"""Traffic kind ``seqeval`` (a fixture): the pool of tiny next-token models
scored back to back, each call in a new order drawn from ``--seed``."""
import time

import numpy as np


def _score(ctx, order):
    import reference
    import tiny_lm_model

    model = ctx.config["model"]
    t_wall, t0 = time.time(), time.monotonic()
    fitness = []
    for i in order:
        genome = ctx.pool[i]
        start = reference.seeded_weights(ctx.seed, model["vocab"], genome["width"])
        _, loss = tiny_lm_model.train({k: np.asarray(v, np.float32) for k, v in start.items()}, ctx.tokens,
                                      ctx.batches, model["learning_rates"][genome["lr"]], steps=model["steps"])
        fitness.append(-float(loss))
    per_genome = np.empty(len(order))
    per_genome[order] = fitness
    return {"scored": len(order), "trained": len(order), "fitness": fitness,
            "failed": int((~np.isfinite(fitness)).sum()), "per_genome": per_genome,
            "calls": [(t_wall, time.monotonic() - t0, len(order))]}


def setup(ctx, mix):
    return {"rng": np.random.default_rng([ctx.seed, 0xA1]), "warmup": _score(ctx, np.arange(len(ctx.pool)))}


def unit(ctx, mix, state):
    return _score(ctx, state["rng"].permutation(len(ctx.pool)))


def checks(ctx, mix, state, units):
    worst = max((float(np.abs(u["per_genome"] - state["warmup"]["per_genome"]).max()) for u in units), default=0.0)
    return [{"name": "order_diff", "value": worst, "limit": 0.0, "ok": worst == 0.0}]
