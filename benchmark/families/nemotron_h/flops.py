"""Executed matrix-product FLOPs and bytes of the Nemotron-H share, by configuration.

``m`` is the family's model block (``family.model_block``).  The counts are of
*executed* work, as often as the program runs it: the train step runs every
block's forward twice (rematerialisation) and its backward once (two products
per forward product), so 4x the forward; the head is outside any
rematerialisation, so 3x.  Elementwise work, norms, softmax, the convolution's
four taps, the gates, the sort and the optimizer are left out, so a share of
peak worked out from these counts is a lower bound on what the chip did.

**The Mamba-2 core** is counted as the chunked form computes it, whatever
implements it, for one held head and chunk of ``Q`` positions at head size ``P``
and state size ``N`` (multiply-adds; a FLOP count is twice that): ``C B'`` once a
*group* (``Q^2 N`` over its heads), the masked product with ``D x`` (``Q^2 P``),
what the chunk adds to the state (``Q P N``) and ``C S_in`` (``Q N P``).  The
carry between chunks is a scaling and an add, no product.  A train step runs
that forward twice and its transpose once (two products a product)
(:func:`state_space_core_flops`).  The least bytes: x, B, C and the step read
and y written, all float32, a forward pass; the same again with the cotangents in
and out a backward pass, and the state of every chunk boundary (``P x N`` float32
a head) written once and read once; ``z`` is the gate's, outside the core.  The
products are float32 (six passes of the matrix unit each, which the count leaves
out): the share is of the chip's bfloat16 peak or its bandwidth, whichever
bounds the count, and says what a kernel could win.

**The attention core** is counted by the (query block, key block) pairs the
fused kernel visits and costs each pair its whole area: ``elements`` of the
forward kernel, ``elements_bwd`` of the backward one, at 32 query heads over 2
key-value heads.  The ``train`` span carries what the program read off the
kernel's own table (``q3n_spans.core_visits``); :func:`block_visits` is the same
count by arithmetic, used where a span has none and held against the table in
the tests.  Forward, a pair-element costs ``2 * 2 * head_dim`` FLOPs (scores and
values); the one backward kernel makes five products: ``2 * 5 * head_dim``.

The grouped products are counted from the rows actually routed to the held
experts (``expert_rows``), never from the buffer's size: 2 products of
``2 * moe_latent_size * moe_intermediate_size`` a row and pass.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

TRAIN_PASSES, TRAIN_PASSES_HEAD = 4, 3
CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS = 2, 1  # of the kernels, and of the chunked core, a train step and layer
#: (queries, keys) a grid step of the fused kernel holds at a head size of 128, forward and backward
#: (``models/lfm2_moe.py::_kernel_blocks``; copied, not imported).
KERNEL_BLOCKS = {"forward": (1024, 1024), "backward": (1024, 1024)}


def layers_of(m: Mapping[str, Any], kind: str) -> int:
    return sum(t == kind for t in m["layer_types"])


def mamba_held(m: Mapping[str, Any]) -> tuple:
    """(heads held, groups held) of a mamba2 layer."""
    heads = m["held_mamba_heads"][1] - m["held_mamba_heads"][0]
    return heads, heads * m["n_groups"] // m["mamba_num_heads"]


def expert_mm_flops(m: Mapping[str, Any], rows: float, passes: int) -> float:
    """FLOPs of the two grouped products over ``rows`` routed rows (summed over layers), ``passes`` times."""
    return passes * rows * 2 * 2.0 * m["moe_latent_size"] * m["moe_intermediate_size"]


def expert_mm_bytes(m: Mapping[str, Any], rows: float, passes: int, layer_calls: int) -> float:
    """Least bytes the grouped products move: each row read and written once a
    product in bfloat16, each held expert's two matrices read once a pass and
    routed layer executed.  ``layer_calls`` is layers x steps as the accepted
    reader counts them (every layer kept); the routed ones are their share."""
    lat, f = m["moe_latent_size"], m["moe_intermediate_size"]
    held = m["held_experts"][1] - m["held_experts"][0]
    routed_calls = layer_calls * layers_of(m, "routed") / m["num_hidden_layers"]
    row_bytes = 2 * ((lat + f) + (f + lat)) * rows  # latent -> f, then f -> latent
    return passes * (row_bytes + routed_calls * held * 2 * lat * f * 2)


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
    """Executed FLOPs of the core of ONE attention layer whose kernel makes ``visits`` over ``sequences`` sequences."""
    hd = m["head_dim"]
    per_head = forward_runs * visits["elements"] * 4.0 * hd + backward_runs * visits["elements_bwd"] * 10.0 * hd
    return sequences * m["num_attention_heads"] * per_head


def core_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, forward_runs: int, backward_runs: int) -> float:
    """Least bytes the core of ONE attention layer moves: q read and o written in bfloat16 and the log-sum-exp
    in float32 a query head, k and v read once a key-value head, forward; q, o, do read, dq written, the log-sum-exp
    read a query head and k, v read, dk, dv written a key-value head, backward."""
    hd, nh, nkv = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    forward = nh * (2 * 2 * hd + 4) + nkv * 2 * 2 * hd
    backward = nh * (2 * 4 * hd + 4) + nkv * 2 * 4 * hd
    return sequences * seq_len * (forward_runs * forward + backward_runs * backward)


def state_space_chunk_macs(m: Mapping[str, Any], chunk: int) -> float:
    """Multiply-adds of one held head and chunk, forward (module docstring)."""
    size, state = m["mamba_head_dim"], m["ssm_state_size"]
    per_group = m["mamba_num_heads"] // m["n_groups"]
    return chunk * chunk * state / per_group + chunk * chunk * size + 2.0 * chunk * size * state


def state_space_core_flops(m: Mapping[str, Any], sequences: float, seq_len: int, chunk: int, forward_runs: int,
                           backward_runs: int) -> float:
    """Executed FLOPs of the chunked core of ONE mamba2 layer's held heads: forward ``forward_runs`` times, its
    transpose ``backward_runs`` times (two products a product)."""
    chunks = -(-seq_len // chunk)
    per_head_chunk = (forward_runs + 2.0 * backward_runs) * state_space_chunk_macs(m, chunk)
    return 2.0 * sequences * mamba_held(m)[0] * chunks * per_head_chunk


def state_space_core_bytes(m: Mapping[str, Any], sequences: float, seq_len: int, chunk: int, forward_runs: int,
                           backward_runs: int) -> float:
    """Least bytes the core of ONE mamba2 layer's held heads moves (module docstring), float32."""
    (heads, groups), size, state = mamba_held(m), m["mamba_head_dim"], m["ssm_state_size"]
    operands = heads * size + 2 * groups * state + heads  # x; B, C; the step: a position
    forward = operands + heads * size
    backward = 2 * operands + heads * size
    states = 2 * -(-seq_len // chunk) * heads * size * state  # written by a forward pass, read by the backward pass
    return 4.0 * sequences * (seq_len * (forward_runs * forward + backward_runs * backward) + backward_runs * states)


def linear_flops_per_token(m: Mapping[str, Any]) -> float:
    """Forward product FLOPs of one token outside the cores, the routed experts and the head: a mamba2 layer's
    in-projection (the held heads' z, x and step, their groups' B and C) and its out-projection's held rows; an
    attention layer's q, k, v and output projections; a routed layer's router, its two latent projections and its
    shared expert."""
    h, hd, nh, nkv = m["hidden_size"], m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    heads, groups = mamba_held(m)
    inner = heads * m["mamba_head_dim"]
    mamba = h * (2 * inner + 2 * groups * m["ssm_state_size"] + heads) + inner * h
    attention = h * hd * (nh + 2 * nkv) + nh * hd * h
    routed = h * m["n_routed_experts"] + 2 * h * m["moe_latent_size"] + 2 * h * m["moe_shared_expert_intermediate_size"]
    return 2.0 * (layers_of(m, "mamba2") * mamba + layers_of(m, "full_attention") * attention
                  + layers_of(m, "routed") * routed)


def train_flops(m: Mapping[str, Any], tokens: float, rows: float, seq_len: int,
                visits: Optional[Mapping[str, int]] = None, chunk: Optional[int] = None) -> float:
    """Executed product FLOPs of train steps over ``tokens`` tokens and ``rows`` routed rows; ``visits`` what the
    spans carried of the attention kernel, else :func:`block_visits`; ``chunk`` likewise, else the published
    ``chunk_size``."""
    sequences, runs = tokens / seq_len, (CORE_FORWARD_RUNS, CORE_BACKWARD_RUNS)
    core = layers_of(m, "full_attention") * core_flops(m, visits or block_visits(seq_len), sequences, *runs) \
        + layers_of(m, "mamba2") * state_space_core_flops(m, sequences, seq_len, chunk or m["chunk_size"], *runs)
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    return (tokens * (TRAIN_PASSES * linear_flops_per_token(m) + TRAIN_PASSES_HEAD * head) + core
            + expert_mm_flops(m, rows, TRAIN_PASSES))
