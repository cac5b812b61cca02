"""Model family ``tiny_lm`` (a fixture): what ``run.py`` calls."""
import math

import numpy as np

from correct import after_window, program_side  # noqa: F401


def make_inputs(config, mix, seed, rehearsal=False):
    print("info tiny_lm make_inputs")
    model, rng = config["model"], np.random.default_rng([seed, 0x5E9])
    tokens = rng.integers(0, model["vocab"], size=(config["data"]["n_sequences"], model["context"] + 1))
    tokens[:, -1] = tokens[:, :-1].sum(axis=1) % model["vocab"]  # something to learn
    pool_rng = np.random.default_rng([int(mix["pool_seed"])])
    pool = [{"width": int(pool_rng.choice(model["widths"])), "lr": int(pool_rng.integers(len(model["learning_rates"])))}
            for _ in range(config["population"])]
    batches = rng.integers(0, len(tokens), size=(model["steps"], model["batch"]))
    return {"tokens": tokens.astype(np.int32), "batches": batches.astype(np.int32), "pool": pool}


def window_checks(ctx, units):
    """A fitness here is a negative loss: finite, below zero, and no worse
    than twice a uniform guess."""
    print("info tiny_lm window_checks")
    fitness = [f for u in units for f in u["fitness"]]
    floor = -2.0 * math.log(ctx.config["model"]["vocab"])
    sound = bool(fitness) and all(math.isfinite(f) and floor < f < 0.0 for f in fitness)
    return [{"name": "fitness_is_a_negative_loss", "value": min(fitness, default=float("nan")),
             "limit": f">{floor}", "ok": sound}]
