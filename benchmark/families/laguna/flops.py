"""Executed matrix-product FLOPs and bytes of the Laguna-XS.2 share, by configuration.

``m`` is the family's model block (``family.model_block``).  The counts are of
*executed* work, as often as the program runs it: the train step runs every
layer's forward twice (per-layer rematerialisation) and its backward once (two
products per forward product), so 4x the forward; the head is outside any
rematerialisation, so 3x.  Elementwise work, norms, softmax, rope, the gates'
sigmoids, the sort and the optimizer are left out, so a share of peak worked out
from these counts is a lower bound on what the chip did.

**Every attention layer is counted at its own query heads**
(``num_attention_heads_per_layer``: 48 in a full layer, 64 in a sliding one;
:func:`heads_of` lists a layer type's).  **The attention core** is counted by the
(query block, key block) pairs the fused kernel visits and costs each pair its
whole area: ``visits`` of one layer type, for one head and sequence --
``elements`` of the forward kernel, ``elements_bwd`` of the backward one -- times
the query heads of that type's layers.  The ``train`` span carries what the
program read off the kernel's own table (``attention_kernel_elements_<mask>``)
and the heads of the mask's layers (``attention_heads_<mask>``;
``mel_spans.core_visits``, ``mel_spans.core_heads``); :func:`block_visits` is the
same count by arithmetic from the mask's rule and the kernel's blocks
(``KERNEL_BLOCKS``, a number of the benchmark's own), used where a span has none
(a program that fell back to XLA's blockwise core) and held against the table in
the tests.  Forward, a pair-element costs ``2 * 2 * head_dim`` FLOPs (scores and
values).  The one backward kernel makes five products (the scores again, dK, dQ,
dP, dV): ``2 * 5 * head_dim``.  A train step runs the forward kernel twice (the
layer's forward and its recomputation) and the backward once.
:func:`visible_elements` counts, from the mask's definition alone, the score
elements of one head and sequence that a query may see: over ``elements`` it is
the share of the kernel's work that the mask does not throw away (a window of
512 in blocks of 1,024: a quarter).

The least bytes of the core: each query head's q read and o written in bfloat16
and its log-sum-exp in float32, each key-value head's k and v read (once: its
six or eight query heads share them) a forward pass; q, o, do read, dq written
and the log-sum-exp read a query head, k, v read and dk, dv written a key-value
head in the backward.

The grouped products are counted from the rows actually routed to the held
experts (``expert_rows``), never from the buffer's size: 3 products of
``2 * hidden * moe_intermediate`` a row and pass.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence

TRAIN_PASSES, TRAIN_PASSES_HEAD = 4, 3
CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS = 2, 1  # of the kernels, a train step and layer
#: (queries, keys) a grid step of the fused kernel holds, forward and backward alike, by the layer type's mask
#: (``models/lfm2_moe.py::_ATTN_KERNEL_BLOCKS``, which serves both masks; copied, not imported).
KERNEL_BLOCKS = {"full_attention": (1024, 1024), "sliding_attention": (1024, 1024)}
#: A layer type's mask as the program's spans and counter name it.
MASK_OF = {"full_attention": "causal", "sliding_attention": "window"}


def expert_mm_flops(m: Mapping[str, Any], rows: float, passes: int) -> float:
    """FLOPs of the three grouped products over ``rows`` routed rows (summed over layers), ``passes`` times."""
    return passes * rows * 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_mm_bytes(m: Mapping[str, Any], rows: float, passes: int, layer_calls: int) -> float:
    """Least bytes the grouped products move: each row read and written once a
    product in bfloat16, each held expert's three matrices read once a pass and
    routed layer executed.  ``layer_calls`` counts layers x steps over EVERY kept
    layer (the accepted reader's count, ``num_hidden_layers`` x steps); the dense
    layers hold no expert, so the routed layers' share of them is taken here."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    held = m["held_experts"][1] - m["held_experts"][0]
    row_bytes = 2 * (2 * (h + f) + (f + h)) * rows  # two products hidden->f, one f->hidden
    routed_calls = layer_calls * m["mlp_layer_types"].count("sparse") / len(m["mlp_layer_types"])
    return passes * (row_bytes + routed_calls * held * 3 * h * f * 2)


def block_visits(m: Mapping[str, Any], kind: str, seq_len: int) -> Dict[str, int]:
    """The block pairs that hold a key some query of theirs sees, one head and
    sequence, by the mask's rule: a pair of query block [a, a + bq) and key block
    [c, c + bk) is visited iff its nearest (query, key) is no key ahead
    (a + bq - 1 >= c) and, in a windowed layer, its farthest within the window
    (a - (c + bk - 1) <= sliding_window - 1)."""
    bq, bk = (min(b, seq_len) for b in KERNEL_BLOCKS[kind])
    reach = m["sliding_window"] - 1 if kind == "sliding_attention" else seq_len
    pairs = sum(1 for a in range(0, seq_len, bq) for c in range(0, seq_len, bk)
                if a + bq - 1 >= c and a - (c + bk - 1) <= reach)
    return {"pairs": pairs, "elements": pairs * bq * bk, "pairs_bwd": pairs, "elements_bwd": pairs * bq * bk}


def visible_elements(m: Mapping[str, Any], kind: str, seq_len: int) -> int:
    """The (query, key) pairs of one head and sequence that the mask of a ``kind`` layer lets through: query i sees
    min(i + 1, sliding_window) keys in a windowed layer, i + 1 in a full one."""
    reach = min(m["sliding_window"], seq_len) if kind == "sliding_attention" else seq_len
    return reach * (reach + 1) // 2 + (seq_len - reach) * reach


def heads_of(m: Mapping[str, Any], kind: str) -> List[int]:
    """The query heads of each kept layer of type ``kind``."""
    return [n for n, t in zip(m["num_attention_heads_per_layer"], m["layer_types"]) if t == kind]


def core_flops(m: Mapping[str, Any], visits: Mapping[str, int], sequences: float, forward_runs: int,
               backward_runs: int, heads: float) -> float:
    """Executed FLOPs of the cores of layers with ``heads`` query heads together (a layer type's: the sum over
    its layers) whose kernel makes ``visits`` a head over ``sequences`` sequences."""
    hd = m["head_dim"]
    per_head = forward_runs * visits["elements"] * 4.0 * hd + backward_runs * visits["elements_bwd"] * 10.0 * hd
    return sequences * heads * per_head


def core_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, forward_runs: int, backward_runs: int,
               heads: Sequence[int]) -> float:
    """Least bytes the cores of the layers with ``heads`` query heads each move (module docstring); the same for
    either mask."""
    hd, nkv = m["head_dim"], m["num_key_value_heads"]
    forward = sum(nh * (2 * 2 * hd + 4) + nkv * 2 * 2 * hd for nh in heads)
    backward = sum(nh * (2 * 4 * hd + 4) + nkv * 2 * 4 * hd for nh in heads)
    return sequences * seq_len * (forward_runs * forward + backward_runs * backward)


def layers_of(m: Mapping[str, Any], kind: str) -> int:
    return sum(t == kind for t in m["layer_types"])


def linear_flops_per_token(m: Mapping[str, Any]) -> float:
    """Forward product FLOPs of one token outside the cores, the routed experts and the head: every layer's four
    attention projections and its head gates at the layer's own query heads, the dense layers' SwiGLU, and every
    routed layer's router and shared expert."""
    h, hd, nkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    attention = sum(h * hd * (2 * nh + 2 * nkv) + h * nh for nh in m["num_attention_heads_per_layer"])
    dense = m["mlp_layer_types"].count("dense") * 3 * h * m["intermediate_size"]
    routed = m["mlp_layer_types"].count("sparse") * (h * m["num_experts"] + 3 * h * m["shared_expert_intermediate_size"])
    return 2.0 * (attention + dense + routed)


def train_flops(m: Mapping[str, Any], tokens: float, rows: float, seq_len: int,
                visits: Optional[Mapping[str, Mapping[str, int]]] = None) -> float:
    """Executed product FLOPs of train steps over ``tokens`` tokens and ``rows`` routed rows; ``visits`` by
    layer type (what the spans carried), else :func:`block_visits`."""
    core = sum(core_flops(m, (visits or {}).get(kind) or block_visits(m, kind, seq_len), tokens / seq_len,
                          CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS, sum(heads_of(m, kind)))
               for kind in KERNEL_BLOCKS)
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    return (tokens * (TRAIN_PASSES * linear_flops_per_token(m) + TRAIN_PASSES_HEAD * head) + core
            + expert_mm_flops(m, rows, TRAIN_PASSES))
