"""Model family ``qwen3_next``: what ``run.py`` calls (README.md beside this file).

One expert-parallel rank's share of Qwen3-Next-80B-A3B-Instruct as
``gentun_tpu/models/lfm2_moe.py`` (the routed family's module: the configuration
says which architecture) trains and scores it, through the accepted traffic kind
``lmpopeval``: Gated DeltaNet layers (``linear_attention``: a chunked scan over
time) three to one with gated full attention at a head size of 256 with rope on a
quarter of a head, 512 routed experts 10 a token with their weights normalised
over the chosen, one shared expert behind a sigmoid gate in every layer, no dense
layer.  Beside this file: ``reference.py`` (the plain float32 reference, the delta
rule one position at a time), ``correct.py`` (the comparison: ``program_side`` in
set-up, ``after_window`` once the window has closed), ``flops.py`` (executed
product FLOPs and bytes), ``scope_rules.py`` (the op classes of its programs) and
``q3n_spans.py`` (what the ``q3n_*`` readers share).  A process loads one family:
what ``families/mellum/`` has alike is copied here, not imported.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from correct import after_window, program_side  # noqa: F401  (the family's contract)

#: The keys of the configuration file that the reference reads as published.
PUBLISHED = ("hidden_size", "head_dim", "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts",
             "num_experts_per_tok", "num_attention_heads", "num_key_value_heads", "num_hidden_layers", "vocab_size",
             "rms_norm_eps", "rope_theta", "partial_rotary_factor", "linear_num_key_heads", "linear_num_value_heads",
             "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim")
#: What the program does for these published settings; any other is not this family's.
FIXED = {"model_type": "qwen3_next", "norm_topk_prob": True, "tie_word_embeddings": False, "hidden_act": "silu",
         "use_sliding_window": False, "rope_scaling": None, "decoder_sparse_step": 1, "mlp_only_layers": []}


def layer_types(config: Dict[str, Any]) -> List[str]:
    """The published pattern: every ``full_attention_interval``-th layer is ``full_attention``, the others
    ``linear_attention`` (the ``qwen3_next`` model type's rule; the config carries the interval, not a list)."""
    every = config["full_attention_interval"]
    return ["full_attention" if (l + 1) % every == 0 else "linear_attention" for l in range(max(config["layers_kept"]) + 1)]


def model_block(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model as the reference and the counts take it: the published keys of
    the configuration file under their published names (``num_hidden_layers``
    is the layers kept and ``layer_types`` their types, picked from the
    published pattern by ``layers_kept``), the experts held."""
    wrong = {k: config.get(k, "absent") for k, v in FIXED.items() if config.get(k, "absent") != v}
    assert not wrong, f"the qwen3_next family runs {FIXED}; the configuration says {wrong}"
    kept = config["layers_kept"]
    assert len(kept) == config["num_hidden_layers"], "layers_kept against num_hidden_layers"
    assert config["shared_expert_intermediate_size"] == config["moe_intermediate_size"], \
        "the shared expert is one expert of the routed experts' width"
    m = {k: config[k] for k in PUBLISHED}
    m["layer_types"] = [layer_types(config)[l] for l in kept]
    first = config["first_expert_held"]
    m["held_experts"] = [first, first + config["num_experts_held"]]
    m["train_steps"] = config["train_steps"]
    return m


def model_params(config: Dict[str, Any], seed: int, rehearsal: bool) -> Dict[str, Any]:
    """The keyword arguments of ``Lfm2MoeModel`` (the routed family's model
    class) that make it this architecture: the published keys; ``layer_types``,
    which say where the delta rule runs and where gated attention; a gate on
    the attention's output and one on the shared expert; ``seed`` is the
    model's own (starting weights, batch order).  The router is a softmax over
    all experts (the ``qwen3_next`` model type's; the config has no key for it)
    and balance is a term of the loss whose weight is the recipe's."""
    m = model_block(config)
    params = {k: m[k] for k in ("hidden_size", "head_dim", "moe_intermediate_size", "num_experts",
                                "num_experts_per_tok", "num_attention_heads", "num_key_value_heads", "vocab_size",
                                "rope_theta", "partial_rotary_factor", "linear_num_key_heads", "linear_num_value_heads",
                                "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim")}
    params.update(layer_types=tuple(m["layer_types"]), layer_ids=tuple(config["layers_kept"]), num_dense_layers=0,
                  intermediate_size=config["intermediate_size"], held_experts=tuple(m["held_experts"]),
                  norm_eps=m["rms_norm_eps"], qk_norm=True, attn_output_gate=True, n_shared_experts=1,
                  shared_expert_gate=True, scoring_func="softmax",
                  norm_topk_prob=config["norm_topk_prob"], balance_rule="aux_loss",
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
    search holds once those are selected out.  The genome is the ``aux_loss``
    balance rule's (``genes.deepseek_v2_genome``: the four genes of every routed
    recipe and ``aux_alpha``, the balance term's weight)."""
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
    starting weights, all from the seed, as in the LFM2 and DeepSeek-V2-Lite
    cells; the pool of recipes from the mix's ``pool_seed``.  The check's tokens
    (``check_x``, ``check_y``) are the window's own."""
    data = config["data"]
    tokens = markov_tokens(data, config["vocab_size"], config["n_sequences"], data["seq_len"], seed)
    pool = make_pool(config["population"], [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
    x, y = tokens[:, :-1], tokens[:, 1:]
    return {"params": model_params(config, seed, rehearsal), "x": x, "y": y, "check_x": x, "check_y": y,
            "pool": pool, "model": model_block(config)}


def window_checks(ctx, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """What the window's answers themselves must satisfy: a fitness is minus a
    mean cross-entropy over the held slice, so finite and -fitness in
    (0, ln(vocab) + 0.5), the accepted routed cells' limit on every recipe (the
    seeded start reads ln(vocab) + 0.41 as DeepSeek-V2-Lite's does: a normed
    state against head rows of deviation 0.02 over 2,048 channels); and the
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
