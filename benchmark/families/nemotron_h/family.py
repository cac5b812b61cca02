"""Model family ``nemotron_h``: what ``run.py`` calls (README.md beside this file).

One rank's share of NVIDIA-Nemotron-3-Super-120B-A12B as
``gentun_tpu/models/lfm2_moe.py`` (the routed family's module: the configuration
says which architecture) trains and scores it, through the accepted traffic kind
``lmpopeval``: blocks that are ONE of a Mamba-2 mixer, an attention and a routed
feed-forward under one norm (the published ``hybrid_override_pattern``: ``M``,
``*``, ``E``); the Mamba-2 mixer held by a share of its heads (whole groups, with
their B and C); attention without a positional encoding at 16 query heads to a
key-value head; 512 experts 22 a token under the sigmoid-with-bias rule, of two
matrices and a squared ReLU, working in a 1,024-wide latent state between a
down- and an up-projection, the routed sum scaled by 5 beside one unscaled shared
expert.  Beside this file: ``reference.py`` (the plain float32 reference),
``correct.py`` (the comparison: ``program_side`` in set-up, ``after_window`` once
the window has closed), ``flops.py`` (executed product FLOPs and bytes),
``scope_rules.py`` (the op classes of its programs) and ``q3n_spans.py`` (what
the cell's readers share, and why under that name).
A process loads one family: what ``families/laguna/`` and ``families/qwen3_next/``
have alike is copied here, not imported.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from correct import after_window, program_side  # noqa: F401  (the family's contract)

#: The keys of the configuration file that the reference reads as published.
PUBLISHED = ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "mamba_num_heads", "mamba_head_dim",
             "n_groups", "ssm_state_size", "conv_kernel", "chunk_size", "n_routed_experts", "num_experts_per_tok",
             "moe_intermediate_size", "moe_latent_size", "moe_shared_expert_intermediate_size", "routed_scaling_factor",
             "layer_norm_epsilon", "vocab_size", "num_hidden_layers", "rope_theta", "time_step_min", "time_step_max",
             "time_step_floor")
#: What the program does for these published settings; any other is not this family's.
FIXED = {"model_type": "nemotron_h", "tie_word_embeddings": False, "attention_bias": False, "use_bias": False,
         "mamba_proj_bias": False, "mlp_bias": False, "use_conv_bias": True, "mlp_hidden_act": "relu2",
         "mamba_hidden_act": "silu", "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
         "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001}
#: The published pattern's letters as the routed module's layer types.
PATTERN = {"M": "mamba2", "*": "full_attention", "E": "routed"}


def layer_types(pattern: str, kept) -> List[str]:
    """The kept layers' types off the published ``hybrid_override_pattern``; an unknown letter is refused."""
    unknown = sorted(set(pattern) - set(PATTERN))
    if unknown:
        raise ValueError(f"hybrid_override_pattern has letters {unknown} beside {sorted(PATTERN)} (M: a Mamba-2 mixer, "
                         f"*: attention, E: a routed feed-forward)")
    return [PATTERN[pattern[l]] for l in kept]


def model_block(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model as the reference and the counts take it: the published keys of
    the configuration file under their published names (``num_hidden_layers``
    is the layers kept; ``layer_types`` their types, picked off the published
    pattern by ``layers_kept``), the experts and the Mamba-2 heads held."""
    wrong = {k: config.get(k, "absent") for k, v in FIXED.items() if config.get(k, "absent") != v}
    assert not wrong, f"the nemotron_h family runs {FIXED}; the configuration says {wrong}"
    kept = config["layers_kept"]
    assert len(kept) == config["num_hidden_layers"], "layers_kept against num_hidden_layers"
    assert config["mamba_num_heads"] * config["mamba_head_dim"] == config["expand"] * config["hidden_size"], "expand"
    m = {k: config[k] for k in PUBLISHED}
    m["layer_types"] = layer_types(config["hybrid_override_pattern"], kept)
    first, head = config["first_expert_held"], config["first_mamba_head_held"]
    m["held_experts"] = [first, first + config["num_experts_held"]]
    m["held_mamba_heads"] = [head, head + config["mamba_heads_held"]]
    m["train_steps"] = config["train_steps"]
    return m


def model_params(config: Dict[str, Any], seed: int, rehearsal: bool) -> Dict[str, Any]:
    """The keyword arguments of ``Lfm2MoeModel`` (the routed family's model
    class) that make it this architecture: the published keys; ``layer_types``,
    where ``routed`` makes every block one half under one norm; the Mamba-2
    heads held; no positional encoding; experts of two matrices under
    ``relu2`` in a latent state, the routed sum's factor and one shared expert
    of its own width; ``seed`` is the model's own (starting weights, batch
    order).  Balance is the router bias, stepped outside the gradient by the
    recipe's ``bias_step`` (``assumed.router``)."""
    m = model_block(config)
    params = {k: m[k] for k in ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "mamba_num_heads",
                                "mamba_head_dim", "ssm_state_size", "num_experts_per_tok", "moe_intermediate_size",
                                "moe_latent_size", "routed_scaling_factor", "vocab_size", "rope_theta")}
    params.update(layer_types=tuple(m["layer_types"]), layer_ids=tuple(config["layers_kept"]), num_dense_layers=0,
                  num_experts=m["n_routed_experts"], held_experts=tuple(m["held_experts"]),
                  mamba_n_groups=m["n_groups"], mamba_conv_kernel=m["conv_kernel"], mamba_chunk=m["chunk_size"],
                  held_mamba_heads=tuple(m["held_mamba_heads"]), norm_eps=m["layer_norm_epsilon"], qk_norm=False,
                  positional_encoding="none", mlp_hidden_act=config["mlp_hidden_act"],
                  n_shared_experts=config["n_shared_experts"],
                  shared_expert_intermediate_size=m["moe_shared_expert_intermediate_size"], scoring_func="sigmoid",
                  norm_topk_prob=True, balance_rule="bias", route_eps=1e-20,
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
    ranges; a draw hotter than ``log10_lr_max`` is drawn again (a recipe that
    diverges inside its few steps collapses its routing, and both its work and
    its loss then follow the seed: PERF.md, PR 28 and PR 32).  The genome is
    the ``bias`` balance rule's (``genes.lfm2_moe_genome``)."""
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

    **The window's pool**: the recipes come from the mix's ``pool_seed``; the
    seed of their starting weights and the tokens (``x``, ``y``) from the
    configuration's ``window_seed`` where it states one, as
    ``families/laguna/family.py`` has it and for its reason (the rate follows
    the routing that the start draws: ``assumed.window_inputs`` has the
    readings), else from ``--seed``; ``--seed`` gives the window the order of
    each call (``traffic_kinds/lmpopeval.py``).

    **The check's inputs come from ``--seed``**: its tokens (``check_x``,
    ``check_y``), its weights, its bias, its batches (``correct.py``)."""
    data, window_seed = config["data"], int(config.get("window_seed", seed))
    window, check = (markov_tokens(data, config["vocab_size"], config["n_sequences"], data["seq_len"], s)
                     for s in (window_seed, seed))
    pool = make_pool(config["population"], [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
    return {"params": model_params(config, window_seed, rehearsal), "x": window[:, :-1], "y": window[:, 1:],
            "check_x": check[:, :-1], "check_y": check[:, 1:], "pool": pool, "model": model_block(config)}


def window_checks(ctx, units: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """What the window's answers themselves must satisfy: a fitness is minus a
    mean cross-entropy over the held slice, so finite and -fitness in
    (0, ln(vocab) + 0.5), the accepted routed cells' limit on every recipe; and
    the pool's mean loss under the configuration's ceiling (the steps learned
    something)."""
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
