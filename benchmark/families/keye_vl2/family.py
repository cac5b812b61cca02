"""Model family ``keye_vl2``: what ``run.py`` calls (README.md beside this file).

One expert-parallel rank's share of the language model of Keye-VL-2.0-30B-A3B as
``gentun_tpu/models/lfm2_moe.py`` (the routed family's module: the configuration
says which architecture) trains and scores it, through the accepted traffic kind
``lmpopeval``: every layer grouped-query attention whose keys a learned indexer
chooses (16 heads of 64 over one shared key head, the 2,048 best keys a query;
the indexer trained beside the trunk by a KL term of its own, its input
detached), q/k norm, rope by sections over three position streams, then 128
routed experts 8 a token with their weights normalised over the chosen, no
shared expert and no dense layer.  Beside this file: ``reference.py`` (the plain
float32 reference, which selects by ``lax.top_k``), ``correct.py`` (the
comparison: ``program_side`` in set-up, ``after_window`` once the window has
closed), ``flops.py`` (the model's and the executed product FLOPs and bytes),
``scope_rules.py`` (the op classes of its programs) and ``mel_spans.py`` (what
the cell's readers share, and why under that name).  A process loads one family:
what ``families/mellum/`` and ``families/laguna/`` have alike is copied here, not
imported.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from correct import after_window, program_side  # noqa: F401  (the family's contract)

#: The keys of the configuration file that the reference reads as published.
PUBLISHED = ("hidden_size", "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
             "num_attention_heads", "num_key_value_heads", "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta")
#: ``sa_config``'s sizes as the model block holds them, flat.
INDEXER = ("indexer_num_heads", "indexer_head_dim", "topk")
#: What the program does for these published settings; any other is not this family's.
FIXED = {"model_type": "KeyeVL2", "norm_topk_prob": True, "tie_word_embeddings": False, "attention_bias": False,
         "hidden_act": "silu", "use_sliding_window": False, "decoder_sparse_step": 1, "mlp_only_layers": []}


def model_block(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model as the reference and the counts take it: the published keys of
    the configuration file under their published names (``num_hidden_layers``
    is the layers kept), ``sa_config``'s indexer sizes and ``topk`` flat,
    ``rope_scaling``'s ``mrope_section``, the experts held."""
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    assert not wrong, f"the keye_vl2 family runs {FIXED}; the configuration says {wrong}"
    assert len(config["layers_kept"]) == config["num_hidden_layers"], "layers_kept against num_hidden_layers"
    sa, rope = config["sa_config"], config["rope_scaling"]
    assert sa["indexer_num_kv_heads"] == 1, "the indexer's heads share ONE key head"
    assert rope.get("rope_type", "default") == "default", rope
    m = {k: config[k] for k in PUBLISHED}
    m.update({k: sa[k] for k in INDEXER})
    m["mrope_section"] = list(rope["mrope_section"])
    first = config["first_expert_held"]
    m["held_experts"] = [first, first + config["num_experts_held"]]
    m["train_steps"] = config["train_steps"]
    return m


def model_params(config: Dict[str, Any], seed: int, rehearsal: bool) -> Dict[str, Any]:
    """The keyword arguments of ``Lfm2MoeModel`` (the routed family's model
    class) that make it this architecture: the published keys; every layer a
    ``sparse_attention`` layer with ``sa_config``'s indexer; the norm of q and k;
    rope by ``mrope_section``; ``seed`` is the model's own (starting weights,
    batch order).  The router is a softmax over all experts (Qwen3-MoE's sparse
    block) and balance is a term of the loss whose weight is the recipe's."""
    m = model_block(config)
    params = {k: m[k] for k in ("hidden_size", "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                                "num_attention_heads", "num_key_value_heads", "vocab_size", "rope_theta",
                                "indexer_num_heads", "indexer_head_dim")}
    params.update(layer_types=("sparse_attention",) * m["num_hidden_layers"], layer_ids=tuple(config["layers_kept"]),
                  num_dense_layers=0, intermediate_size=config["intermediate_size"], held_experts=tuple(m["held_experts"]),
                  norm_eps=m["rms_norm_eps"], qk_norm=True, sparse_topk=m["topk"], mrope_section=tuple(m["mrope_section"]),
                  scoring_func="softmax", norm_topk_prob=config["norm_topk_prob"], balance_rule="aux_loss",
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
    """What the window trains on and what the check compares, apart.

    **The window's pool is one fixed pool, whole**, as ``families/mellum/family.py``
    and ``families/laguna/family.py`` have it: the recipes come from the mix's
    ``pool_seed``, and the seed of their starting weights and the tokens
    (``x``, ``y``) from the configuration's ``window_seed``; ``--seed`` gives
    the window the order of each call (``traffic_kinds/lmpopeval.py``) and
    nothing else.  With both from ``--seed`` (tried first: PERF.md, PR 49) the
    rate read 209.56-210.85 ind/h/chip over six whole runs, quartiles 0.52%
    apart against the half-bound of 0.5% that admits a cell (the routing the
    starting weights and tokens draw decides how many layer-steps take the
    worst-case row buffer), and on one seed of eight a recipe of the accepted
    pool diverged (loss 14.2, ``correct`` false): a pool holds no recipe that
    diverges.  ``window_seed`` is the median draw of the five sound ones
    (the configuration's ``assumed.window_inputs``).

    **The check's inputs come from ``--seed``**: its tokens (``check_x``,
    ``check_y``), its weights, its batches (``correct.py``)."""
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
    configuration's ceiling (the steps learned something).  The fitness is a
    validation cross-entropy: no balance term, no indexer's loss."""
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
