"""Model family ``laguna``: what ``run.py`` calls (README.md beside this file).

One expert-parallel rank's share of Laguna-XS.2 as
``gentun_tpu/models/lfm2_moe.py`` (the routed family's module: the configuration
says which architecture) trains and scores it, through the accepted traffic kind
``lmpopeval``: full and sliding-window attention mixed 1:3 whose layer type gives a
layer its mask, its rope, the share of a head that rope turns AND its query heads
(48 full, 64 windowed at a window of 512; 8 key-value heads in both), a sigmoid
gate of one scalar a head on attention's output, a dense leading layer, then 256
routed experts 8 a token under the sigmoid-with-bias rule with the routed sum
scaled by 2.5 beside one unscaled shared expert.  Beside this file:
``reference.py`` (the plain float32 reference), ``correct.py`` (the comparison:
``program_side`` in set-up, ``after_window`` once the window has closed),
``flops.py`` (executed product FLOPs and bytes), ``scope_rules.py`` (the op
classes of its programs) and ``mel_spans.py`` (what the cell's readers share, and
why under that name).
A process loads one family: what ``families/mellum/`` and ``families/qwen3_next/``
have alike is copied here, not imported.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from correct import after_window, program_side  # noqa: F401  (the family's contract)

#: The keys of the configuration file that the reference reads as published.
PUBLISHED = ("hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
             "num_experts", "num_experts_per_tok", "num_key_value_heads", "num_hidden_layers", "vocab_size",
             "rms_norm_eps", "sliding_window", "moe_routed_scaling_factor")
#: The published lists with one entry a layer, cut to the layers kept.
PER_LAYER = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
#: What the program does for these published settings; any other is not this family's.
FIXED = {"model_type": "laguna", "tie_word_embeddings": False, "attention_bias": False, "gating": True,
         "moe_apply_router_weight_on_input": False}


def model_block(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model as the reference and the counts take it: the published keys of
    the configuration file under their published names (``num_hidden_layers``
    is the layers kept; ``layer_types``, ``mlp_layer_types`` and
    ``num_attention_heads_per_layer`` their entries, picked from the published
    lists by ``layers_kept``; ``rope_parameters`` its layer types' blocks), the
    experts held."""
    wrong = {k: config.get(k, "absent") for k, v in FIXED.items() if config.get(k, "absent") != v}
    assert not wrong, f"the laguna family runs {FIXED}; the configuration says {wrong}"
    kept = config["layers_kept"]
    assert len(kept) == config["num_hidden_layers"], "layers_kept against num_hidden_layers"
    assert config["shared_expert_intermediate_size"] == config["moe_intermediate_size"], \
        "the shared expert is one expert of the routed experts' width"
    m = {k: config[k] for k in PUBLISHED}
    m.update({k: [config[k][l] for l in kept] for k in PER_LAYER})
    dense = m["mlp_layer_types"].count("dense")
    assert m["mlp_layer_types"] == ["dense"] * dense + ["sparse"] * (len(kept) - dense), "dense layers lead"
    m["rope_parameters"] = {kind: dict(block) for kind, block in config["rope_parameters"].items()
                            if isinstance(block, dict)}
    first = config["first_expert_held"]
    m["held_experts"] = [first, first + config["num_experts_held"]]
    m["train_steps"] = config["train_steps"]
    return m


def model_params(config: Dict[str, Any], seed: int, rehearsal: bool) -> Dict[str, Any]:
    """The keyword arguments of ``Lfm2MoeModel`` (the routed family's model
    class) that make it this architecture: the published keys; ``layer_types``,
    which choose each layer's mask, rope and rotated share, and
    ``num_attention_heads_per_layer`` its query heads; a gate a head on the
    attention's output; the leading dense layers; one shared expert and the
    factor on the routed sum; ``seed`` is the model's own (starting weights,
    batch order).  The router is the sigmoid-with-bias rule (``assumed.router``)
    and balance is that bias, stepped outside the gradient by the recipe's
    ``bias_step``."""
    m = model_block(config)
    params = {k: m[k] for k in ("hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size", "num_experts",
                                "num_experts_per_tok", "num_key_value_heads", "vocab_size", "sliding_window",
                                "rope_parameters")}
    params.update(layer_types=tuple(m["layer_types"]), layer_ids=tuple(config["layers_kept"]),
                  num_attention_heads=config["num_attention_heads"],
                  num_attention_heads_per_layer=tuple(m["num_attention_heads_per_layer"]),
                  num_dense_layers=m["mlp_layer_types"].count("dense"), held_experts=tuple(m["held_experts"]),
                  norm_eps=m["rms_norm_eps"], qk_norm=False, attn_head_gate=True, n_shared_experts=1,
                  scoring_func="sigmoid", norm_topk_prob=True, balance_rule="bias",
                  routed_scaling_factor=m["moe_routed_scaling_factor"],
                  tie_word_embeddings=config["tie_word_embeddings"], train_steps=config["train_steps"],
                  seed=seed % (2**31 - 1), **config["run"])
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
    ranges; a draw hotter than ``log10_lr_max`` is drawn again: a recipe that
    diverges inside its few steps collapses its routing, and both its work and
    its loss then follow the seed (PERF.md, PR 28 and PR 32): the pool is what a
    search holds once those are selected out.  The genome is the ``bias``
    balance rule's (``genes.lfm2_moe_genome``: the four genes of every routed
    recipe and ``bias_step``, the router bias's step)."""
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

    **The window's pool is one fixed pool, whole**, as ``families/mellum/family.py``
    has it: the recipes come from the mix's ``pool_seed``, and the seed of
    their starting weights and the tokens (``x``, ``y``) from the
    configuration's ``window_seed``; ``--seed`` gives the window the order of
    each call (``traffic_kinds/lmpopeval.py``) and nothing else.  With both from
    ``--seed`` the rate followed the routing that the starting weights and the
    tokens draw: 668-686 ind/h/chip over seven whole runs, quartiles 2.3% apart
    against a bound of 1% (PERF.md, PR 42).  ``window_seed`` is the median draw
    of those seven (the configuration's ``assumed.window_inputs``).

    **The check's inputs come from ``--seed``**: its tokens (``check_x``,
    ``check_y``), its weights, its bias, its batches (``correct.py``)."""
    data, window_seed = config["data"], int(config["window_seed"])
    window, check = (markov_tokens(data, config["vocab_size"], config["n_sequences"], data["seq_len"], s)
                     for s in (window_seed, seed))
    pool = make_pool(config["population"], [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
    return {"params": model_params(config, window_seed, rehearsal), "x": window[:, :-1], "y": window[:, 1:],
            "check_x": check[:, :-1], "check_y": check[:, 1:], "pool": pool, "model": model_block(config)}


def window_checks(ctx, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """What the window's answers themselves must satisfy: a fitness is minus a
    mean cross-entropy over the held slice, so finite and -fitness in
    (0, ln(vocab) + 0.5), the accepted routed cells' limit on every recipe (the
    seeded start reads ln(vocab) + 0.41: a normed state against head rows of
    deviation 0.02 over 2,048 channels); and the pool's mean loss under the
    configuration's ceiling (the steps learned something)."""
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
