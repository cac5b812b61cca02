"""Traffic kind ``popeval``: whole populations scored back to back.

Unit of work: one ``GeneticCnnModel.cross_validate_population`` call on the
cell's pool of genomes (``families/genetic_cnn/family.py::make_pool``), taken in a new order each call.
The order comes from ``--seed``; the pool comes from the mix, so every seed
trains the same architectures, in other slots, from other weights.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np


def _score(ctx, order) -> Dict[str, Any]:
    from gentun_tpu.models.cnn import GeneticCnnModel

    genomes = [ctx.pool[i] for i in order]
    t_wall, t0 = time.time(), time.monotonic()
    fitness = np.asarray(GeneticCnnModel.cross_validate_population(
        ctx.x, ctx.y, genomes, **ctx.params), np.float64)
    wall = time.monotonic() - t0
    per_genome = np.empty(len(order))
    per_genome[order] = fitness
    return {"scored": len(order), "trained": len(order), "fitness": fitness.tolist(),
            "failed": int((~np.isfinite(fitness)).sum()), "order": np.asarray(order),
            "per_genome": per_genome, "calls": [(t_wall, wall, len(order))]}


def setup(ctx, mix) -> Dict[str, Any]:
    """Warm-up: the pool once in its own order; compiles or loads every
    program the window will run."""
    state = {"rng": np.random.default_rng([ctx.seed, 0xA1])}
    state["warmup"] = _score(ctx, np.arange(len(ctx.pool)))
    return state


def unit(ctx, mix, state) -> Dict[str, Any]:
    return _score(ctx, state["rng"].permutation(len(ctx.pool)))


def checks(ctx, mix, state, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Every answer of the window against the warm-up's, genome by genome.

    Fitness is a function of the genome, not of the slot it trains in.  Only
    slots of programs of the same width are compared: a population wider than
    the program (the OOM healer's chunks) ends in a narrower chunk, and
    programs of different widths round differently (PERF.md, PR 21).
    """
    width = int(ctx.config["check"]["program_widths"][0])
    full = (len(ctx.pool) // width) * width
    base = state["warmup"]
    worst = across = 0.0
    for u in units:
        pos = np.empty(len(u["order"]), int)
        pos[u["order"]] = np.arange(len(u["order"]))  # genome -> slot of this call
        same = (pos < full) & (np.arange(len(pos)) < full)
        diff = np.abs(u["per_genome"] - base["per_genome"])
        if same.any():
            worst = max(worst, float(np.nanmax(diff[same])))
        if (~same).any():
            across = max(across, float(np.nanmax(diff[~same])))
    limit = ctx.config["check"]["limits"]["slot_diff"]
    asked = ctx.monitor.requests_in_window()
    print(f"info slot_diff_across_program_widths={across} (not compared)")
    return [{"name": "slot_diff", "value": worst, "limit": limit, "ok": bool(worst <= limit)},
            {"name": "compiles_in_window", "value": asked, "limit": 0, "ok": asked == 0}]
