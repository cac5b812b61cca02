"""Executed matrix-product FLOPs and bytes of the DeepSeek-V2-Lite share, by configuration.

``m`` is the family's model block (``family.model_block``).  The counts are of
*executed* work, as often as the program runs it: the train step runs every
layer's forward twice (per-layer rematerialisation) and its backward once (two
products per forward product), so 4x the forward; the head is outside any
rematerialisation, so 3x.  Elementwise work, norms, softmax, rope, the sort and
the optimizer are left out, so a share of peak worked out from these counts is
a lower bound on what the chip did.

**The causal core** is counted from the fused kernel's blocks
(``models/lfm2_moe.py::_ATTN_KERNEL_BLOCKS``: 1,024 queries x 1,024 keys, forward
and backward; copied here as ``CORE_BLOCK``, a number of the benchmark's own),
never from ``attn_block``, and the same whatever implements the core: a block
pair is visited if any of its keys is at or before any of its queries, and costs
its whole area.  Forward, a pair-element costs ``2 * (qk + v)`` FLOPs (scores over
the ``qk = nope + rope`` columns, values over ``v``).  The one backward kernel makes
five products: the scores again and dK, dQ over ``qk``; dP and dV over ``v``:
``2 * (3 qk + 2 v)``.  A train step runs the forward kernel twice (the layer's
forward and its recomputation) and the backward once.  The zero columns the
program pads q and k with (192 as 256) are not model work and are left out.
What differs from ``families/lfm2_moe/flops.py``: there ``keys_seen`` goes by
``attn_block`` (512) and ``TRAIN_PASSES_ATTENTION_CORE`` is 5 forward-equivalents,
both of the blockwise core that family's TPU programs no longer run (PERF.md §7).

The least bytes of the core: q, k, v read and o written in bfloat16 and the
log-sum-exp in float32 a forward pass; q, k, v, o, do read, dq, dk, dv written
and the log-sum-exp read in the backward.

The grouped products are counted from the rows actually routed to the held
experts (``expert_rows``), never from the buffer's size: 3 products of
``2 * hidden * moe_intermediate`` a row and pass.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

TRAIN_PASSES, TRAIN_PASSES_HEAD = 4, 3
CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS = 2, 1  # of the kernels, a train step and layer
CORE_BLOCK = 1024  # queries and keys a grid step of the fused kernel holds, forward and backward


def expert_mm_flops(m: Mapping[str, Any], rows: float, passes: int) -> float:
    """FLOPs of the three grouped products over ``rows`` routed rows (summed over layers), ``passes`` times."""
    return passes * rows * 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_mm_bytes(m: Mapping[str, Any], rows: float, passes: int, layer_calls: int) -> float:
    """Least bytes the grouped products move: each row read and written once a
    product in bfloat16, each held expert's three matrices read once a pass and
    routed layer executed (``layer_calls``: layers x steps)."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    held = m["held_experts"][1] - m["held_experts"][0]
    row_bytes = 2 * (2 * (h + f) + (f + h)) * rows  # two products hidden->f, one f->hidden
    return passes * (row_bytes + layer_calls * held * 3 * h * f * 2)


def core_pair_elements(seq_len: int) -> float:
    """(query, key) pairs the kernel's causal blocks cover in one sequence and head."""
    block = min(CORE_BLOCK, seq_len)
    blocks = seq_len // block
    return block * block * blocks * (blocks + 1) / 2.0


def core_flops(m: Mapping[str, Any], sequences: float, seq_len: int, forward_runs: int, backward_runs: int) -> float:
    """Executed FLOPs of the causal core of ONE latent layer over ``sequences`` sequences."""
    qk, v = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    per_pair = forward_runs * 2.0 * (qk + v) + backward_runs * 2.0 * (3 * qk + 2 * v)
    return sequences * m["num_attention_heads"] * core_pair_elements(seq_len) * per_pair


def core_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, forward_runs: int, backward_runs: int) -> float:
    """Least bytes the core of ONE latent layer moves (module docstring)."""
    qk, v = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    forward = 2 * (2 * qk + 2 * v) + 4
    backward = 2 * (4 * qk + 4 * v) + 4
    return sequences * m["num_attention_heads"] * seq_len * (forward_runs * forward + backward_runs * backward)


def forward_flops_per_token(m: Mapping[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward product FLOPs of one token by part, the routed experts' products
    left out (they follow the rows): ``linear`` (the latent operator's four
    projections, dense feed-forward, router, shared experts), ``attention_core``
    (scores and values, by the kernel's blocks), ``head``."""
    h, nh = m["hidden_size"], m["num_attention_heads"]
    qk, v, rank, rope = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"], \
        m["qk_rope_head_dim"]
    n_layers, n_dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    latent = 2.0 * (h * nh * qk + h * (rank + rope) + rank * nh * (qk - rope + v) + nh * v * h)
    routed = 2.0 * h * m["n_routed_experts"] + 6.0 * h * m["n_shared_experts"] * m["moe_intermediate_size"]
    linear = n_layers * latent + n_dense * 6.0 * h * m["intermediate_size"] + (n_layers - n_dense) * routed
    return {"linear": linear, "attention_core": n_layers * core_flops(m, 1.0, seq_len, 1, 0) / seq_len,
            "head": 2.0 * h * m["vocab_size"]}


def train_flops(m: Mapping[str, Any], tokens: float, rows: float, seq_len: int) -> float:
    """Executed product FLOPs of train steps over ``tokens`` tokens and ``rows`` routed rows."""
    per = forward_flops_per_token(m, seq_len)
    core = m["num_hidden_layers"] * core_flops(m, tokens / seq_len, seq_len, CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS)
    return (tokens * (TRAIN_PASSES * per["linear"] + TRAIN_PASSES_HEAD * per["head"]) + core
            + expert_mm_flops(m, rows, TRAIN_PASSES))
