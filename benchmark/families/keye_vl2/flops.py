"""Product FLOPs and bytes of the Keye-VL-2.0 share, by configuration: **the model's** work for the two
roofline shares, **the executed** work for the whole step's share of the peak.

``m`` is the family's model block (``family.model_block``).  The accepted ``mel_*`` readers name a core by a layer
type; in this family ``full_attention`` stands for the masked core and ``sliding_attention`` for the indexer
(``KERNEL_BLOCKS``' keys; ``mel_spans.py`` says why).

**The model's work, whatever implements it** (the roofline shares): a query keeps ``topk`` keys (all of them
where it has no more), so one head and sequence scores :func:`chosen_elements` pairs -- 31,458,304 at 16,384
positions and a ``topk`` of 2,048; the masked core costs each ``2 * 2 * head_dim`` FLOPs forward (scores and
values) and ``2 * 5 * head_dim`` backward (the scores again, dK, dQ, dP, dV), run as the accepted cells' fused
kernel would run them under per-layer rematerialisation: forward twice and backward once a layer and step
(``CORE_FORWARD_RUNS``, ``CORE_BACKWARD_RUNS``), with that kernel's least bytes.  The indexer must score every
causal pair (:func:`causal_elements`) before it can rank them: ``2 * indexer_num_heads * indexer_head_dim`` FLOPs
a pair forward, twice that backward (dqI and dkI), at the same runs.  A later core that skips what the mask hides
does the same model's work in less time: its share rises, and cannot pass 100%.

**The executed work** (``mel_train_mfu_executed``): what the programs of this PR run.  The core is XLA's query
blocks in groups of four, each block against every key up to its group's last query: :func:`block_elements`
pairs a head and sequence, which the
``train`` span carries off the core's own table (``sparse_core_elements``).  The layer keeps the selection and the
core's output for its backward pass, and a block is rematerialised on its own there, so a step runs the core's two
forward products twice and its four backward products once; the indexer's products run once more forward (the
selection's pass) and their two backward products once.  Elementwise work (the softmax over all those pairs, the 32
counting passes of the selection, relu and weights, norms, rope) is left out, as in every family's counts.

The grouped products are counted from the rows actually routed to the held experts (``expert_rows``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

TRAIN_PASSES, TRAIN_PASSES_HEAD = 4, 3
CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS = 2, 1  # of the model's core, a train step and layer: the accepted cells' convention
#: What this PR's programs execute a train step and layer: forward runs of the core's two products and of the
#: indexer's one, and the backward products of each (run once).
EXECUTED = {"core_forward_runs": 2, "core_backward_products": 4, "indexer_forward_runs": 3, "indexer_backward_products": 2}
#: The query block of XLA's core (``run.attn_block`` of the cell; copied, not imported), by the accepted readers' names.
KERNEL_BLOCKS = {"full_attention": (512, 512), "sliding_attention": (512, 512)}
#: The query blocks a group of the core's table holds (``models/lfm2_moe.py::_SPARSE_GROUP``; copied, not imported).
GROUP = 4


def expert_mm_flops(m: Mapping[str, Any], rows: float, passes: int) -> float:
    """FLOPs of the three grouped products over ``rows`` routed rows (summed over layers), ``passes`` times."""
    return passes * rows * 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_mm_bytes(m: Mapping[str, Any], rows: float, passes: int, layer_calls: int) -> float:
    """Least bytes the grouped products move: each row read and written once a product in bfloat16, each held
    expert's three matrices read once a pass and layer executed (every layer is routed)."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    held = m["held_experts"][1] - m["held_experts"][0]
    row_bytes = 2 * (2 * (h + f) + (f + h)) * rows  # two products hidden->f, one f->hidden
    return passes * (row_bytes + layer_calls * held * 3 * h * f * 2)


def causal_elements(seq_len: int) -> int:
    """The (query, key) pairs of one sequence with the key no later than the query."""
    return seq_len * (seq_len + 1) // 2


def chosen_elements(m: Mapping[str, Any], seq_len: int) -> int:
    """The pairs one head and sequence attends: every key of a query with no more than ``topk``, ``topk`` of every other's."""
    top = min(m["topk"], seq_len)
    return top * (top + 1) // 2 + (seq_len - top) * top


def block_elements(seq_len: int, block: int) -> Dict[str, int]:
    """What XLA's blockwise core visits a head and sequence, by arithmetic: query blocks in groups of ``GROUP``,
    each block against every key up to its group's last query (held against the program's own table in the tests)."""
    block = min(block, seq_len)
    groups = [(first, min(first + GROUP * block, seq_len)) for first in range(0, seq_len, GROUP * block)]
    return {"pairs": seq_len // block, "elements": sum((last - first) * last for first, last in groups)}


def core_flops(m: Mapping[str, Any], elements: float, sequences: float, forward_runs: int, backward_runs: int) -> float:
    """The model's FLOPs of the masked cores of every kept layer over ``sequences`` sequences, ``elements`` pairs a head."""
    hd = m["head_dim"]
    per_head = elements * hd * (forward_runs * 4.0 + backward_runs * 10.0)
    return sequences * m["num_hidden_layers"] * m["num_attention_heads"] * per_head


def core_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, forward_runs: int, backward_runs: int) -> float:
    """Least bytes of the masked cores: each query head's q read and o written in bfloat16 and its log-sum-exp in
    float32, each key-value head's k and v read a forward pass; q, o, do read, dq written and the log-sum-exp read
    a query head, k, v read and dk, dv written a key-value head in the backward; a threshold a query either way."""
    hd, nh, nkv = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    forward = nh * (2 * 2 * hd + 4) + nkv * 2 * 2 * hd + 4
    backward = nh * (2 * 4 * hd + 4) + nkv * 2 * 4 * hd + 4
    return sequences * m["num_hidden_layers"] * seq_len * (forward_runs * forward + backward_runs * backward)


def indexer_flops(m: Mapping[str, Any], elements: float, sequences: float, forward_runs: int, backward_runs: int) -> float:
    """The model's FLOPs of the indexers' score products over ``elements`` pairs a sequence: one product of
    ``indexer_head_dim`` a head and pair forward, two (dqI, dkI) backward."""
    per_pair = 2.0 * m["indexer_num_heads"] * m["indexer_head_dim"]
    return sequences * m["num_hidden_layers"] * elements * per_pair * (forward_runs + 2.0 * backward_runs)


def indexer_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, forward_runs: int, backward_runs: int) -> float:
    """Least bytes of the indexers: qI, kI in bfloat16 and w in float32 read, a threshold written a query forward;
    the same read and their three cotangents written backward."""
    ni, di = m["indexer_num_heads"], m["indexer_head_dim"]
    forward = 2 * (ni * di + di) + 4 * ni + 4
    backward = 2 * forward
    return sequences * m["num_hidden_layers"] * seq_len * (forward_runs * forward + backward_runs * backward)


def linear_flops_per_token(m: Mapping[str, Any]) -> float:
    """Forward product FLOPs of one token outside the cores, the indexers' scores, the routed experts and the
    head: every layer's four attention projections, the indexer's three and the router."""
    h, hd, nh, nkv = m["hidden_size"], m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    ni, di = m["indexer_num_heads"], m["indexer_head_dim"]
    attention = h * hd * (2 * nh + 2 * nkv)
    indexer = h * (ni * di + di + ni)
    return 2.0 * m["num_hidden_layers"] * (attention + indexer + h * m["num_experts"])


def train_flops(m: Mapping[str, Any], tokens: float, rows: float, seq_len: int,
                visits: Optional[Mapping[str, Optional[Mapping[str, int]]]] = None) -> float:
    """EXECUTED product FLOPs of train steps over ``tokens`` tokens and ``rows`` routed rows; ``visits`` by the
    accepted readers' layer types (what the spans carried off the core's table), else :func:`block_elements`."""
    found = (visits or {}).get("full_attention") or block_elements(seq_len, KERNEL_BLOCKS["full_attention"][0])
    elements, sequences, layers = found["elements"], tokens / seq_len, m["num_hidden_layers"]
    core = sequences * layers * m["num_attention_heads"] * elements * m["head_dim"] * 2.0 * (
        2 * EXECUTED["core_forward_runs"] + EXECUTED["core_backward_products"])
    indexer = sequences * layers * elements * 2.0 * m["indexer_num_heads"] * m["indexer_head_dim"] * (
        EXECUTED["indexer_forward_runs"] + EXECUTED["indexer_backward_products"])
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    return (tokens * (TRAIN_PASSES * linear_flops_per_token(m) + TRAIN_PASSES_HEAD * head) + core + indexer
            + expert_mm_flops(m, rows, TRAIN_PASSES))
