"""The fixture's comparison: the system's compiled trainer against the plain
reference, one genome of every width, from the same seeded weights and rows."""
import numpy as np

import reference
import tiny_lm_model


def program_side(ctx):
    print("info tiny_lm program_side")
    model = ctx.config["model"]
    out = []
    for i, width in enumerate(model["widths"]):
        start = reference.seeded_weights(ctx.seed + i, model["vocab"], width)
        lr = model["learning_rates"][i % len(model["learning_rates"])]
        _, final = tiny_lm_model.train({k: np.asarray(v, np.float32) for k, v in start.items()},
                                       ctx.tokens, ctx.batches, lr, steps=model["steps"])
        out.append({"start": start, "lr": lr, "loss": float(final)})
    return out


def after_window(ctx, state, control=None):
    print("info tiny_lm after_window")
    steps = ctx.config["model"]["steps"]
    gap = max(abs(s["loss"] - reference.train(s["start"], ctx.tokens, ctx.batches[:steps], s["lr"])[1])
              for s in state)
    limit = ctx.config["check"]["limits"]["loss_gap"]
    return [{"name": "loss_gap", "value": gap, "limit": limit, "ok": bool(gap <= limit)}], None
