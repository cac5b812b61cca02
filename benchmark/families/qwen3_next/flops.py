"""Executed matrix-product FLOPs and bytes of the Qwen3-Next share, by configuration.

``m`` is the family's model block (``family.model_block``).  The counts are of
*executed* work, as often as the program runs it: the train step runs every
layer's forward twice (rematerialisation) and its backward once (two products
per forward product), so 4x the forward; the head is outside any
rematerialisation, so 3x.  Elementwise work, norms, softmax, rope, the
convolution's four taps, the gates, the sort and the optimizer are left out, so
a share of peak worked out from these counts is a lower bound on what the chip
did.

**The delta rule's core** is counted as the chunked form computes it, whatever
implements it, for one value head and chunk of ``C`` positions at key size
``dk`` and value size ``dv`` (multiply-adds; a FLOP count is twice that):
``k k'`` and ``q k'`` once a *key* head (``2 C^2 dk`` over its value heads); the
unit lower-triangular system by substitution, ``C (C - 1) / 2`` rows of ``dv +
dk`` columns; and the scan's four products ``W S``, ``q S``, ``(q k') V`` and
``k' V``: ``3 C dk dv + C^2 dv``.  A train step runs that forward twice and its
transpose once (two products a product), and the scan's body once more: its
interior is rematerialised when a chunk is differentiated (:func:`delta_core_flops`).
The least bytes: q and k a key head, v, g, beta and the output a value head, all
float32, a forward pass; the same again with the cotangents in and out a backward
pass, and the state of every chunk boundary (``dk x dv`` float32 a value head)
written once and read once.  The products are float32 (six passes of the matrix
unit each, which the count leaves out): the share is of the chip's bfloat16 peak
or its bandwidth, whichever bounds the count, and says what a kernel could win.

**The full-attention core** is counted by the (query block, key block) pairs the
fused kernel visits and costs each pair its whole area: ``elements`` of the
forward kernel, ``elements_bwd`` of the backward one (whose query block is half
as tall at a head size of 256: ``models/lfm2_moe.py::_kernel_blocks``).  The
``train`` span carries what the program read off the kernel's own table
(``q3n_spans.core_visits``); :func:`block_visits` is the same count by
arithmetic, used where a span has none and held against the table in the tests.
Forward, a pair-element costs ``2 * 2 * head_dim`` FLOPs (scores and values); the
one backward kernel makes five products: ``2 * 5 * head_dim``.

The grouped products are counted from the rows actually routed to the held
experts (``expert_rows``), never from the buffer's size: 3 products of
``2 * hidden * moe_intermediate`` a row and pass.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

TRAIN_PASSES, TRAIN_PASSES_HEAD = 4, 3
CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS = 2, 1  # of the kernels, and of the chunked core, a train step and layer
#: (queries, keys) a grid step of the fused kernel holds at a head size of 256, forward and backward
#: (``models/lfm2_moe.py::_kernel_blocks``; copied, not imported).
KERNEL_BLOCKS = {"forward": (1024, 1024), "backward": (512, 1024)}
#: Positions a chunk of the delta rule holds where a span does not say (``models/lfm2_moe.py::Lfm2MoeConfig.delta_chunk``).
DELTA_CHUNK = 64


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


def block_visits(seq_len: int) -> Dict[str, int]:
    """The block pairs that hold a key some query of theirs sees under the causal mask, one head and sequence."""
    def visited(blocks) -> tuple:
        bq, bk = (min(b, seq_len) for b in blocks)
        pairs = sum(1 for a in range(0, seq_len, bq) for c in range(0, seq_len, bk) if a + bq - 1 >= c)
        return pairs, pairs * bq * bk

    (forward, elements), (backward, elements_bwd) = visited(KERNEL_BLOCKS["forward"]), visited(KERNEL_BLOCKS["backward"])
    return {"pairs": forward, "elements": elements, "pairs_bwd": backward, "elements_bwd": elements_bwd}


def core_flops(m: Mapping[str, Any], visits: Mapping[str, int], sequences: float, forward_runs: int,
               backward_runs: int) -> float:
    """Executed FLOPs of the core of ONE full-attention layer whose kernel makes ``visits`` over ``sequences`` sequences."""
    hd = m["head_dim"]
    per_head = forward_runs * visits["elements"] * 4.0 * hd + backward_runs * visits["elements_bwd"] * 10.0 * hd
    return sequences * m["num_attention_heads"] * per_head


def core_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, forward_runs: int, backward_runs: int) -> float:
    """Least bytes the core of ONE full-attention layer moves: q read and o written in bfloat16 and the log-sum-exp
    in float32 a query head, k and v read once a key-value head, forward; q, o, do read, dq written, the log-sum-exp
    read a query head and k, v read, dk, dv written a key-value head, backward."""
    hd, nh, nkv = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    forward = nh * (2 * 2 * hd + 4) + nkv * 2 * 2 * hd
    backward = nh * (2 * 4 * hd + 4) + nkv * 2 * 4 * hd
    return sequences * seq_len * (forward_runs * forward + backward_runs * backward)


def delta_chunk_macs(m: Mapping[str, Any], chunk: int) -> Dict[str, float]:
    """Multiply-adds of one value head and chunk, forward: ``within`` (the chunk's own: ``k k'``, ``q k'``, the
    system) and ``scan`` (the four products that touch the carried state or the corrected values)."""
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_key_head = m["linear_num_value_heads"] // m["linear_num_key_heads"]
    within = 2.0 * chunk * chunk * dk / per_key_head + chunk * (chunk - 1) / 2.0 * (dv + dk)
    return {"within": within, "scan": 3.0 * chunk * dk * dv + chunk * chunk * dv}


def delta_core_flops(m: Mapping[str, Any], sequences: float, seq_len: int, chunk: int, forward_runs: int,
                     backward_runs: int) -> float:
    """Executed FLOPs of the chunked core of ONE linear-attention layer: forward ``forward_runs`` times, its transpose
    ``backward_runs`` times (two products a product) with the scan's body computed once more beside it."""
    macs = delta_chunk_macs(m, chunk)
    chunks = -(-seq_len // chunk)
    forward = macs["within"] + macs["scan"]
    per_head_chunk = forward_runs * forward + backward_runs * (2.0 * forward + macs["scan"])
    return 2.0 * sequences * m["linear_num_value_heads"] * chunks * per_head_chunk


def delta_core_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, chunk: int, forward_runs: int,
                     backward_runs: int) -> float:
    """Least bytes the core of ONE linear-attention layer moves (module docstring), float32."""
    nk, nv, dk, dv = (m["linear_num_key_heads"], m["linear_num_value_heads"], m["linear_key_head_dim"],
                      m["linear_value_head_dim"])
    operands = 2 * nk * dk + nv * dv + 2 * nv  # q, k; v; g, beta: a position
    forward = operands + nv * dv
    backward = 2 * operands + nv * dv
    states = 2 * -(-seq_len // chunk) * nv * dk * dv  # written by a forward pass, read by the backward pass
    return 4.0 * sequences * (seq_len * (forward_runs * forward + backward_runs * backward) + backward_runs * states)


def layers_of(m: Mapping[str, Any], kind: str) -> int:
    return sum(t == kind for t in m["layer_types"])


def linear_flops_per_token(m: Mapping[str, Any]) -> float:
    """Forward product FLOPs of one token outside the cores, the routed experts and the head: a linear-attention
    layer's in-projections and out-projection, a full-attention layer's q (with its gate), k, v and output
    projections, and every layer's router, shared expert and its gate."""
    h, hd, nh, nkv = m["hidden_size"], m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    keys, values = m["linear_num_key_heads"] * m["linear_key_head_dim"], m["linear_num_value_heads"] * m["linear_value_head_dim"]
    linear = h * (2 * keys + 2 * values) + h * 2 * m["linear_num_value_heads"] + values * h
    full = h * hd * (2 * nh + 2 * nkv) + nh * hd * h
    every = h * m["num_experts"] + 3 * h * m["shared_expert_intermediate_size"] + h
    return 2.0 * (layers_of(m, "linear_attention") * linear + layers_of(m, "full_attention") * full
                  + m["num_hidden_layers"] * every)


def train_flops(m: Mapping[str, Any], tokens: float, rows: float, seq_len: int,
                visits: Optional[Mapping[str, int]] = None, chunk: Optional[int] = None) -> float:
    """Executed product FLOPs of train steps over ``tokens`` tokens and ``rows`` routed rows; ``visits`` what the
    spans carried of the full-attention kernel, else :func:`block_visits`; ``chunk`` likewise, else ``DELTA_CHUNK``."""
    sequences, runs = tokens / seq_len, (CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS)
    core = layers_of(m, "full_attention") * core_flops(m, visits or block_visits(seq_len), sequences, *runs) \
        + layers_of(m, "linear_attention") * delta_core_flops(m, sequences, seq_len, chunk or DELTA_CHUNK, *runs)
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    return (tokens * (TRAIN_PASSES * linear_flops_per_token(m) + TRAIN_PASSES_HEAD * head) + core
            + expert_mm_flops(m, rows, TRAIN_PASSES))
