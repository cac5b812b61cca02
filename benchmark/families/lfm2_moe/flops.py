"""Executed matrix-product FLOPs and bytes of the LFM2-MoE share, by configuration.

``m`` is the family's model block (``family.model_block``).  The counts are of
*executed* work, as often as the program runs it: the train step runs every
layer's forward twice (per-layer rematerialisation) and its backward once (two
products per forward product), so 4x the forward; the score and value products
inside an attention block run a third time (the block is rematerialised inside
the layer's own recomputation), so 5x; the head is outside any
rematerialisation, so 3x.  Attention is counted as the program runs it: query
blocks of ``attn_block`` against the keys up to the block's end.  Elementwise
work, norms, softmax, the sort and the optimizer are left out, so a share of
peak worked out from these counts is a lower bound on what the chip did.

The grouped products are counted from the rows actually routed to the held
experts (``expert_rows``), never from the buffer's size: 3 products of
``2 * hidden * moe_intermediate`` a row and pass.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

TRAIN_PASSES, TRAIN_PASSES_ATTENTION_CORE, TRAIN_PASSES_HEAD = 4, 5, 3


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


def forward_flops_per_token(m: Mapping[str, Any], seq_len: int, attn_block: int) -> Dict[str, float]:
    """Forward product FLOPs of one token by part, the expert products left
    out (they follow the rows): ``linear`` (operators' projections, dense
    feed-forward, router), ``attention_core`` (scores and values, by blocks), ``head``."""
    h = m["hidden_size"]
    kv = m["num_key_value_heads"] * (h // m["num_attention_heads"])
    n_conv = sum(t == "conv" for t in m["layer_types"])
    n_attn = len(m["layer_types"]) - n_conv
    n_routed = len(m["layer_types"]) - m["num_dense_layers"]
    blocks = max(seq_len // min(attn_block, seq_len), 1)
    keys_seen = min(attn_block, seq_len) * (blocks + 1) / 2.0  # mean keys a query's block is multiplied against
    linear = (n_conv * 8.0 * h * h + n_attn * (4.0 * h * h + 4.0 * h * kv)
              + m["num_dense_layers"] * 6.0 * h * m["intermediate_size"] + n_routed * 2.0 * h * m["num_experts"])
    return {"linear": linear, "attention_core": n_attn * 4.0 * h * keys_seen, "head": 2.0 * h * m["vocab_size"]}


def train_flops(m: Mapping[str, Any], tokens: float, rows: float, seq_len: int, attn_block: int) -> float:
    """Executed product FLOPs of train steps over ``tokens`` tokens and ``rows`` routed rows."""
    per = forward_flops_per_token(m, seq_len, attn_block)
    return (tokens * (TRAIN_PASSES * per["linear"] + TRAIN_PASSES_ATTENTION_CORE * per["attention_core"]
                      + TRAIN_PASSES_HEAD * per["head"]) + expert_mm_flops(m, rows, TRAIN_PASSES))
