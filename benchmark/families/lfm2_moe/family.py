"""Model family ``lfm2_moe``: what ``run.py`` calls (README.md beside this file).

One expert-parallel rank's share of LFM2-24B-A2B as ``gentun_tpu/models/lfm2_moe.py``
trains and scores it: token sequences in place of images, a genome that is a
training recipe in place of node-pair bits, a fitness that is minus a
validation loss in place of an accuracy.  Beside this file: ``reference.py``
(the plain float32 reference), ``correct.py`` (the comparison: ``program_side``
in set-up, ``after_window`` once the window has closed), ``flops.py`` (executed
product FLOPs and bytes) and ``scope_rules.py`` (the op classes of its programs).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from correct import after_window, program_side  # noqa: F401  (the family's contract)

#: The keys of the configuration file that the reference reads as published.
PUBLISHED = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
             "num_attention_heads", "num_key_value_heads", "num_dense_layers", "vocab_size", "conv_L_cache",
             "norm_eps", "rope_parameters")


def model_block(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model as the reference and the counts take it: the published keys of
    the configuration file, the layers that are kept, the experts held."""
    m = {k: config[k] for k in PUBLISHED}
    m["layer_types"] = [config["layer_types"][i] for i in config["layers_kept"]]
    first = config["first_expert_held"]
    m["held_experts"] = [first, first + config["num_experts_held"]]
    m["train_steps"] = config["train_steps"]
    assert len(m["layer_types"]) == config["num_hidden_layers"], "layers_kept against num_hidden_layers"
    return m


def model_params(config: Dict[str, Any], seed: int, rehearsal: bool) -> Dict[str, Any]:
    """The keyword arguments of ``Lfm2MoeModel`` for this configuration;
    ``seed`` is the model's own (starting weights, batch order)."""
    m = model_block(config)
    params = {k: m[k] for k in PUBLISHED if k != "rope_parameters"}
    params.update(rope_theta=float(m["rope_parameters"]["rope_theta"]), layer_types=tuple(m["layer_types"]),
                  layer_ids=tuple(config["layers_kept"]), held_experts=tuple(m["held_experts"]),
                  train_steps=config["train_steps"], seed=seed % (2**31 - 1), **config["run"])
    if rehearsal:
        params["cache_dir"] = False
    return params


def markov_tokens(data: Dict[str, Any], vocab: int, n_sequences: int, length: int, seed: int) -> np.ndarray:
    """(n_sequences, length + 1) token ids of a first-order Markov chain over
    the held slice: with probability ``stay`` the next id is a fixed seeded
    permutation of the current one, else a fresh draw from a Zipf law
    (exponent ``zipf``); so the stationary law is Zipf-skewed, routing is
    uneven, and the loss can fall below ln(vocab)."""
    rng = np.random.default_rng([seed, 0x70C5])
    law = 1.0 / np.arange(1, vocab + 1) ** data["zipf"]
    fresh = rng.choice(vocab, size=(n_sequences, length + 1), p=law / law.sum())
    follows = rng.random((n_sequences, length + 1)) < data["stay"]
    successor = rng.permutation(vocab)
    tokens = fresh.copy()
    for t in range(1, length + 1):
        tokens[:, t] = np.where(follows[:, t], successor[tokens[:, t - 1]], fresh[:, t])
    return tokens.astype(np.int32)


def make_pool(size: int, seed, log10_lr_max: float) -> List[Dict[str, float]]:
    """``size`` recipes: the genome's defaults first, the others drawn from its
    ranges; a draw hotter than ``log10_lr_max`` is drawn again.  At the published
    width a recipe above about 10^-3.4 diverges inside its few steps (the loss
    goes from 9.4 to 20 at the third), its routing collapses onto a few experts
    or off the held ones, and both its work and its loss then follow the seed
    (PERF.md, PR 28): the pool is what a search holds once those are selected out."""
    from gentun_tpu.genes import lfm2_moe_genome

    spec, rng, pool = lfm2_moe_genome(), np.random.default_rng(seed), []
    pool.append(spec.default())
    while len(pool) < size:
        recipe = spec.sample(rng)
        if recipe["log10_lr"] <= log10_lr_max:
            pool.append(recipe)
    return pool


def make_inputs(config: Dict[str, Any], mix: Dict[str, Any], seed: int, rehearsal: bool = False) -> Dict[str, Any]:
    """What the window trains on and what the check compares, apart.

    **The window's pool is one fixed pool, whole**, as the four later routed
    configurations have it: the recipes come from the mix's ``pool_seed``, and
    the seed of their starting weights and the tokens (``x``, ``y``) from the
    configuration's ``window_seed``; ``--seed`` gives the window the order of
    each call (``traffic_kinds/lmpopeval.py``) and nothing else.  A routed
    model's work follows its routing, and the routing follows the starting
    weights and the tokens: with both from ``--seed`` the rate ran from
    1,146 to 1,160 ind/h/chip by seed, the same seed reading the same rate
    again, and the driver's check refused the cell (quartiles 0.47-0.63% of
    the median apart against a bound of 1%: PERF.md, PR 51).  So every seed
    does the same work, as every seed scores the same recipes.
    ``window_seed`` is the median draw of the eleven measured (the
    configuration's ``assumed.window_inputs``).

    **The check's inputs come from ``--seed``** as they always did: its tokens
    (``check_x``, ``check_y``), its weights, its bias, its batches (``correct.py``)."""
    data, window_seed = config["data"], int(config["window_seed"])
    window, check = (markov_tokens(data, config["vocab_size"], config["n_sequences"], data["seq_len"], s)
                     for s in (window_seed, seed))
    pool = make_pool(config["population"], [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
    return {"params": model_params(config, window_seed, rehearsal), "x": window[:, :-1], "y": window[:, 1:],
            "check_x": check[:, :-1], "check_y": check[:, 1:], "pool": pool, "model": model_block(config)}


def window_checks(ctx, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """What the window's answers themselves must satisfy: a fitness is minus a
    mean cross-entropy over the held slice, so finite and -fitness in
    (0, ln(vocab) + 0.5): the seeded start reads ln(vocab) + 0.41 (logits of
    deviation 0.9) and no recipe of the pool ends above it (a recipe that
    diverges can: the mix keeps those out of the pool); and the pool's mean
    loss under the configuration's ceiling (the steps learned something)."""
    loss = [-f for u in units for f in u["fitness"]]
    top = math.log(ctx.config["vocab_size"]) + 0.5
    in_range = bool(loss) and all(math.isfinite(v) and 0.0 < v < top for v in loss)
    mean = float(np.mean(loss)) if loss else float("nan")
    ceiling = ctx.config["check"]["loss_mean_ceiling"]
    return [
        {"name": "loss_in_range", "value": max(loss, default=float("nan")), "limit": f"(0, {top:.4f})", "ok": in_range},
        {"name": "loss_mean_ceiling", "value": mean, "limit": f"<{ceiling}",
         "ok": ctx.rehearsal or (bool(loss) and mean < ceiling)},
    ]
