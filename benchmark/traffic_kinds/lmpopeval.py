"""Traffic kind ``lmpopeval``: a pool of training recipes scored back to back.

Unit of work: one ``Lfm2MoeModel.cross_validate_population`` call on the cell's
pool of genomes (``families/lfm2_moe/family.py::make_pool``: the recipe's
defaults and draws from its ranges), taken in a new order each call, closed
loop, the fitness cache bypassed.  The order comes from ``--seed``; the pool
comes from the mix (``pool_seed``; no recipe hotter than ``pool_log10_lr_max``);
the tokens and the seed of the recipes' starting weights are what the family's
``make_inputs`` hands over: one fixed draw (the configuration's ``window_seed``)
in every routed configuration but DeepSeek-V2-Lite's and Qwen3-Next's, whose
come from ``--seed``.
A routed model's work follows its routing: a token law with many effective ids
and a pool none of whose recipes diverges keep it nearly alike from seed to
seed (PERF.md, PR 28), and only one draw for every seed keeps it alike
(PERF.md, PRs 34, 42, 46, 51).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np


def _score(ctx, order) -> Dict[str, Any]:
    from gentun_tpu.models.lfm2_moe import Lfm2MoeModel

    genomes = [ctx.pool[i] for i in order]
    t_wall, t0 = time.time(), time.monotonic()
    fitness = np.asarray(Lfm2MoeModel.cross_validate_population(ctx.x, ctx.y, genomes, **ctx.params), np.float64)
    wall = time.monotonic() - t0
    per_genome = np.empty(len(order))
    per_genome[order] = fitness
    return {"scored": len(order), "trained": len(order), "fitness": fitness.tolist(),
            "failed": int((~np.isfinite(fitness)).sum()), "order": np.asarray(order),
            "per_genome": per_genome, "calls": [(t_wall, wall, len(order))]}


def setup(ctx, mix) -> Dict[str, Any]:
    """Warm-up: the pool once in its own order; compiles or loads every program the window will run."""
    state = {"rng": np.random.default_rng([ctx.seed, 0xA1])}
    state["warmup"] = _score(ctx, np.arange(len(ctx.pool)))
    return state


def unit(ctx, mix, state) -> Dict[str, Any]:
    return _score(ctx, state["rng"].permutation(len(ctx.pool)))


def checks(ctx, mix, state, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Every answer of the window against the warm-up's, genome by genome: a
    fitness is a function of the genome, not of its position in the call nor
    of who trained before it (a donated buffer that leaked one individual's
    state into the next would show here).  And no program asked for in the window."""
    worst = max((float(np.nanmax(np.abs(u["per_genome"] - state["warmup"]["per_genome"]))) for u in units),
                default=0.0)
    limit = ctx.config["check"]["limits"]["order_diff"]
    asked = ctx.monitor.requests_in_window()
    return [{"name": "order_diff", "value": worst, "limit": limit, "ok": bool(worst <= limit)},
            {"name": "compiles_in_window", "value": asked, "limit": 0, "ok": asked == 0}]
