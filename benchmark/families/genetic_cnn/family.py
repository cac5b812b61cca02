"""Model family ``genetic_cnn``: what ``run.py`` calls (README.md, "A model family").

The Genetic-CNN of ``gentun_tpu/models/cnn.py``: images with a class label,
genomes of one bit per ordered node pair of a stage, a fitness that is a
validation accuracy.  Beside this file: ``reference.py`` (the plain float32
reference), ``correct.py`` (the comparison: ``program_side`` in set-up,
``after_window`` once the window has closed), ``flops.py`` (executed conv+dense
FLOPs) and ``scope_rules.py`` (the op classes of its two programs).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from correct import after_window, program_side  # noqa: F401  (the family's contract)


def synthetic_images(data: Dict[str, Any], seed: int):
    """Class prototypes plus noise (``bench.synthetic_cifar``, any class count)."""
    rng = np.random.default_rng([seed, 0xDA7A])
    shape, classes = tuple(data["input_shape"]), data["n_classes"]
    protos = rng.normal(size=(classes, *shape)).astype(np.float32)
    y = rng.integers(0, classes, size=data["n"]).astype(np.int32)
    x = protos[y] + data["noise"] * rng.normal(size=(data["n"], *shape)).astype(np.float32)
    return x, y


def make_pool(nodes, size: int, seed) -> List[Dict[str, tuple]]:
    """``size`` random genomes: one bit per ordered node pair of each stage."""
    rng = np.random.default_rng(seed)
    return [{f"S_{s + 1}": tuple(int(b) for b in rng.integers(0, 2, size=k * (k - 1) // 2))
             for s, k in enumerate(nodes)} for _ in range(size)]


def make_inputs(config: Dict[str, Any], mix: Dict[str, Any], seed: int, rehearsal: bool = False) -> Dict[str, Any]:
    """Model parameters, images, labels and the pool of genomes, all from the
    seed but the pool, which a mix may fix so that every seed does the same work."""
    model = config["model"]
    params = {k: tuple(v) if isinstance(v, list) else v for k, v in model.items()}
    params["seed"] = seed % (2**31 - 1)
    if rehearsal:
        params["cache_dir"] = False
    x, y = synthetic_images(config["data"], seed)
    pool = make_pool(model["nodes"], config["population"] * int(mix.get("pool_populations", 1)),
                     [int(mix["pool_seed"])] if "pool_seed" in mix else [seed, 0x9001])
    return {"params": params, "x": x, "y": y, "pool": pool}


def window_checks(ctx, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """What the window's answers themselves must satisfy: a fitness is an
    accuracy, so finite and in [0, 1], and the mean of those trained above the
    configuration's floor (just above chance: it catches a pass that answers
    one class everywhere)."""
    fitness = [f for u in units for f in u["fitness"]]
    floor = ctx.config["check"]["fitness_mean_floor"]
    in_range = all(math.isfinite(f) and 0.0 <= f <= 1.0 for f in fitness)
    mean = float(np.mean(fitness)) if fitness else float("nan")
    return [
        {"name": "fitness_in_unit_interval", "value": int(in_range), "limit": 1, "ok": in_range},
        {"name": "fitness_mean_floor", "value": mean, "limit": f">{floor}",
         "ok": ctx.rehearsal or (bool(fitness) and mean > floor)},
    ]
