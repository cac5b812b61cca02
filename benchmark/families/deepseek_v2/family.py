"""Model family ``deepseek_v2``: what ``run.py`` calls (README.md beside this file).

One expert-parallel rank's share of DeepSeek-V2-Lite as ``gentun_tpu/models/lfm2_moe.py``
(the routed family's module: the configuration says which architecture) trains
and scores it, through the accepted traffic kind ``lmpopeval``: latent
attention, shared experts beside the routed ones, a softmax router and a
balance term in the loss.  Beside this file: ``reference.py`` (the plain float32
reference), ``correct.py`` (the comparison: ``program_side`` in set-up,
``after_window`` once the window has closed), ``flops.py`` (executed product
FLOPs and bytes), ``scope_rules.py`` (the op classes of its programs) and
``dsv2_spans.py`` (what the ``dsv2_*`` readers share).  A process loads one
family: what ``families/lfm2_moe/`` has alike is copied here, not imported.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from correct import after_window, program_side  # noqa: F401  (the family's contract)

#: The keys of the configuration file that the reference reads as published.
PUBLISHED = ("hidden_size", "intermediate_size", "moe_intermediate_size", "n_routed_experts", "num_experts_per_tok",
             "n_shared_experts", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "first_k_dense_replace", "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
             "rope_scaling")
#: What the program's router and loss do for these published settings; any other is not this family's.
ROUTING = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1, "topk_group": 1, "norm_topk_prob": False,
           "routed_scaling_factor": 1, "seq_aux": True, "tie_word_embeddings": False, "q_lora_rank": None}


def model_block(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model as the reference and the counts take it: the published keys of
    the configuration file under their published names (``num_hidden_layers``
    is the layers kept: the leading dense one and the routed ones after it),
    the experts held."""
    wrong = {k: config.get(k) for k, v in ROUTING.items() if config.get(k) != v}
    assert not wrong, f"the deepseek_v2 family runs {ROUTING}; the configuration says {wrong}"
    m = {k: config[k] for k in PUBLISHED}
    first = config["first_expert_held"]
    m["held_experts"] = [first, first + config["num_experts_held"]]
    m["train_steps"] = config["train_steps"]
    assert len(config["layers_kept"]) == m["num_hidden_layers"], "layers_kept against num_hidden_layers"
    return m


def model_params(config: Dict[str, Any], seed: int, rehearsal: bool) -> Dict[str, Any]:
    """The keyword arguments of ``Lfm2MoeModel`` (the routed family's model
    class) that make it this architecture; ``seed`` is the model's own
    (starting weights, batch order)."""
    m = model_block(config)
    params = {k: m[k] for k in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
                                "n_shared_experts", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                                "qk_rope_head_dim", "v_head_dim", "vocab_size", "rope_scaling")}
    params.update(layer_types=("latent_attention",) * m["num_hidden_layers"], layer_ids=tuple(config["layers_kept"]),
                  num_dense_layers=m["first_k_dense_replace"], num_experts=m["n_routed_experts"],
                  held_experts=tuple(m["held_experts"]), norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
                  scoring_func=config["scoring_func"], norm_topk_prob=config["norm_topk_prob"],
                  balance_rule="aux_loss", tie_word_embeddings=config["tie_word_embeddings"],
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
    ranges; a draw hotter than ``log10_lr_max`` is drawn again: a recipe that
    diverges inside its few steps collapses its routing, and both its work and
    its loss then follow the seed (PERF.md, PR 28 and PR 32): the pool is what a
    search holds once those are selected out."""
    from gentun_tpu.genes import deepseek_v2_genome

    spec, rng, pool = deepseek_v2_genome(), np.random.default_rng(seed), []
    pool.append(spec.default())
    while len(pool) < size:
        recipe = spec.sample(rng)
        if recipe["log10_lr"] <= log10_lr_max:
            pool.append(recipe)
    return pool


def make_inputs(config: Dict[str, Any], mix: Dict[str, Any], seed: int, rehearsal: bool = False) -> Dict[str, Any]:
    """Tokens (``x``) and next tokens (``y``) and the seed of the recipes'
    starting weights, all from the seed; the pool of recipes from the mix's
    ``pool_seed``."""
    data = config["data"]
    tokens = markov_tokens(data, config["vocab_size"], config["n_sequences"], data["seq_len"], seed)
    pool = make_pool(config["population"], [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
    return {"params": model_params(config, seed, rehearsal), "x": tokens[:, :-1], "y": tokens[:, 1:],
            "pool": pool, "model": model_block(config)}


def window_checks(ctx, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """What the window's answers themselves must satisfy: a fitness is minus a
    mean cross-entropy over the held slice, so finite and -fitness in
    (0, ln(vocab) + 0.5): the seeded start reads ln(vocab) + 0.41 (logits of
    deviation 0.9: a normed state against head rows of deviation 0.02 over
    2,048 channels) and no recipe of the pool ends above it (a
    recipe that diverges can: the mix keeps those out of the pool); and the
    pool's mean loss under the configuration's ceiling (the steps learned
    something).  The fitness is a validation cross-entropy: no balance term."""
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
