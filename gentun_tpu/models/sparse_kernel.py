"""The masked core of a ``sparse_attention`` layer as three Pallas TPU kernels: forward, backward, and the heads' share.

The function is :func:`gentun_tpu.models.lfm2_moe._sparse_core`'s, which stays as XLA's query blocks, as the path of
every backend that is no TPU and as the oracle these kernels are tested against: each query head is softmax attention
over the keys its query KEPT, a mask that is data (the indexer's choice, a bit a query and key).  What differs is where
the scores live.  XLA's blocks write every head's float32 scores to memory, mask them, soft-max them, cast them and
differentiate the lot (1.2 TB moved a step at the published shape); here a tile's scores, the running maximum, sum and
accumulator stay in fast memory from the operands to the output, and the choice is read as bits.

**The bits, as planes.**  The choice is packed (:func:`packed`) so that a kernel unpacks it with two integer ops on
native tiles: keys come in super-tiles of ``SUPER`` = 4,096, a super-tile is 128 int32 words a query, and **bit ``b`` of
word ``l`` is key ``128 b + l`` of the super-tile**.  A (queries, 128) block of words then serves 4,096 keys, and the
mask of the 128 keys of plane ``b`` is ``(words >> b) & 1``: elementwise on whole tiles, the shift a scalar.  (Eight
keys a byte in key order would be a lane interleave inside a kernel.)  The one layout everywhere: XLA's query blocks
read it too (:func:`unpacked`), so nothing chooses between two.

**The kernels.**  All three walk (query tile, key tile) pairs up to the diagonal (:func:`_last_tile`; a tile past it is
skipped and its fetch clamped to the last live one) and none is skipped for being empty: their cost is the causal
area's.  A grid step holds one key-value head's ``group`` query heads, which share the tile's mask.  Masked entries are
scored ``NEG``, a large negative FINITE value: a query whose first kept key lies in a late tile carries garbage in its
sum and accumulator until then, which ``exp(m_old - m_new) = 0`` wipes when that key arrives, and every query keeps at
least one key (its own position is among its candidates), so every masked entry weighs exactly 0 at the end.

- ``sparse_core_fwd``: ``out`` (the compute dtype) and the log-sum-exp (float32, a head and query) by online softmax.
- ``sparse_core_bwd``: dq, dk, dv from the operands, ``out``, the log-sum-exp and the output's cotangent, the scores
  recomputed a tile at a time: one kernel, five products a tile.  The query tiles are the outer loop, so dq accumulates
  in scratch; a key-value head's dk and dv (length x head size, float32) stay in fast memory for the head's whole walk
  and are written once, which is what bounds the length the kernels take (:func:`fits`).
- ``sparse_core_share``: a query block's ``p[t, s] = mean_h exp(score_h - lse_h)`` on kept pairs, 0 elsewhere, float32
  (queries, keys): what the indexer's loss reads, a constant to the gradient.  It masks with the same bits.

The log-sum-exp is held (sequences, kv heads, length, group): the queries along the sublanes, as a tile's scores have
them, so a head's column is a lane of the block and nothing is transposed in a kernel.
"""
import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The keys a (queries, 128) int32 block of bits holds: bit ``b`` of lane ``l`` is key ``128 b + l`` of the super-tile.
SUPER, LANES = 4096, 128
#: What a masked score reads: finite, so a row with no kept key so far computes ``exp(0)`` and not ``exp(nan)``.
NEG = float(-0.7 * np.finfo(np.float32).max)
#: Of the chip's 128 MiB of fast memory, what a kernel may be given (the compiler's default is 16 MiB), and what the
#: backward kernel's resident dk and dv (two buffers each) may take of it.
VMEM_LIMIT, RESIDENT_BYTES = 96 * 2 ** 20, 32 * 2 ** 20
#: The query heads of a key-value head a grid step holds, at most: compiled for the described v5e at 8 (the published
#: shape) and 16 (``tests/test_delta_kernel_compiles.py``); at 32 the forward kernel's scratch (a maximum and a sum a
#: head and query, held 128 lanes wide, and the accumulators) asks for 109 MB.
MAX_GROUP = 16


class Dims(NamedTuple):
    """The static sizes of a call: the (queries, keys) a grid step of a kernel holds, what a product of a query and a
    key is multiplied by to be a score (in float32, on the tile), the names the forward's residuals are kept under
    (``out``'s, the log-sum-exp's), and whether Pallas interprets the kernels (the CPU's tests)."""
    tile: Tuple[int, int]
    scale: float
    names: Tuple[str, str]
    interpret: bool = False


def words(keys: int) -> int:
    """The int32 words a query's choice among ``keys`` keys takes: 128 a super-tile, whole super-tiles."""
    return -(-keys // SUPER) * LANES


def packed(kept):
    """A mask (..., keys) as planes of bits (the module's docstring): int32 (..., ``words(keys)``)."""
    pad = -kept.shape[-1] % SUPER
    if pad:
        kept = jnp.pad(kept, [(0, 0)] * (kept.ndim - 1) + [(0, pad)])
    planes = kept.reshape(*kept.shape[:-1], -1, 32, LANES).astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)[:, None]
    whole = jax.lax.bitcast_convert_type(jnp.sum(planes, axis=-2, dtype=jnp.uint32), jnp.int32)
    return whole.reshape(*kept.shape[:-1], -1)


def unpacked(bits, keys: int):
    """:func:`packed` undone for the first ``keys`` keys: bool (..., keys)."""
    whole = jax.lax.bitcast_convert_type(bits[..., : words(keys)], jnp.uint32)
    planes = (whole.reshape(*bits.shape[:-1], -1, 1, LANES) >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & jnp.uint32(1)
    return planes.reshape(*bits.shape[:-1], -1)[..., :keys] == 1


def _last_tile(query_tile, queries: int, keys: int):
    """The last key tile a query tile visits: the one that holds its last query's own position.  The kernels'
    table: their index maps, their bodies and :func:`visits` all read it."""
    return ((query_tile + 1) * queries - 1) // keys


def visits(length: int, tile: Tuple[int, int]) -> Dict[str, int]:
    """What the kernels visit for one head and sequence, off their own table (:func:`_last_tile`): the (query tile,
    key tile) ``tiles`` a kernel walks, the score ``elements`` in them of the forward kernel and ``elements_bwd`` of
    the backward kernel (one run of the share kernel over every query block of the loss pass walks the same)."""
    queries, keys = tile
    walked = sum(_last_tile(t, queries, keys) + 1 for t in range(length // queries))
    return {"tiles": walked, "elements": walked * queries * keys, "elements_bwd": walked * queries * keys}


def fits(length: int, group: int, size: int, block: int, reach: int, tile: Tuple[int, int]) -> bool:
    """Whether the compiled kernels take this shape: a head of whole 128 lanes, a length of whole super-tiles of
    the bits and of whole tiles, key tiles of whole planes that divide a super-tile, the loss pass's query block of
    ``block`` whole query tiles and the keys its groups reach (multiples of ``reach``, and the length) whole key tiles
    (:func:`heads_share`'s grid), a key-value head's dk and dv within their part of fast memory and no more query
    heads a key-value head than a grid step was seen to hold."""
    queries, keys = tile
    whole = length % queries == 0 and length % keys == 0 and queries % 8 == 0 and keys % LANES == 0 and SUPER % keys == 0
    return (size % LANES == 0 and length % SUPER == 0 and whole and block % queries == 0 and reach % keys == 0
            and 4 * length * size * 4 <= RESIDENT_BYTES and group <= MAX_GROUP)


def _nt(a, b):
    """``a b'``, float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a' b``, float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _bias(bits_ref, key_tile, keys: int):
    """A tile's mask as what is added to its scores: 0 where the query kept the key, ``NEG`` elsewhere; float32
    (queries, keys), from the block of words (1, queries, 128) that holds the tile's super-tile."""
    words = bits_ref[0]
    first = (key_tile % (SUPER // keys)) * (keys // LANES)  # the tile's first plane
    planes = [jnp.bitwise_and(jnp.right_shift(words, first + j), 1) for j in range(keys // LANES)]
    kept = planes[0] if len(planes) == 1 else jnp.concatenate(planes, axis=1)
    return jnp.where(kept == 1, 0.0, NEG)


def _forward_kernel(q_ref, k_ref, v_ref, bits_ref, out_ref, lse_ref, m_ref, l_ref, acc_ref, *, queries: int, keys: int, scale: float):
    query_tile, key_tile = pl.program_id(2), pl.program_id(3)
    last = _last_tile(query_tile, queries, keys)
    group = q_ref.shape[2]

    @pl.when(key_tile == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(key_tile <= last)
    def _():
        bias, k, v = _bias(bits_ref, key_tile, keys), k_ref[0, 0], v_ref[0, 0]
        for g in range(group):  # the key-value head's query heads share the tile's mask
            s = _nt(q_ref[0, 0, g], k) * scale + bias
            m_old = m_ref[g]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p, alpha = jnp.exp(s - m_new), jnp.exp(m_old - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    @pl.when(key_tile == last)
    def _():
        for g in range(group):
            out_ref[0, 0, g] = (acc_ref[g] / l_ref[g]).astype(out_ref.dtype)
            lse_ref[0, 0, :, g:g + 1] = m_ref[g] + jnp.log(l_ref[g])


def _backward_kernel(q_ref, k_ref, v_ref, bits_ref, out_ref, lse_ref, sent_ref, dq_ref, dk_ref, dv_ref, dq_acc, di_ref, *,
                     queries: int, keys: int, scale: float):
    query_tile, key_tile = pl.program_id(2), pl.program_id(3)
    last = _last_tile(query_tile, queries, keys)
    group = q_ref.shape[2]

    @pl.when((query_tile == 0) & (key_tile == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(key_tile == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for g in range(group):  # sum_d dO O: what a row's softmax takes off every dP
            di_ref[g] = jnp.sum(sent_ref[0, 0, g].astype(jnp.float32) * out_ref[0, 0, g].astype(jnp.float32), axis=1, keepdims=True)

    @pl.when(key_tile <= last)
    def _():
        bias, k, v = _bias(bits_ref, key_tile, keys), k_ref[0, 0], v_ref[0, 0]
        dk, dv = jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)
        for g in range(group):
            q, sent = q_ref[0, 0, g], sent_ref[0, 0, g]
            p = jnp.exp(_nt(q, k) * scale + bias - lse_ref[0, 0, :, g:g + 1])
            dv = dv + _tn(p.astype(sent.dtype), sent)
            ds = (p * (_nt(sent, v) - di_ref[g])).astype(q.dtype)  # the score's cotangent; the product's is ``scale`` times it
            dk = dk + _tn(ds, q)
            dq_acc[g] = dq_acc[g] + jnp.dot(ds, k, preferred_element_type=jnp.float32)
        at = pl.ds(pl.multiple_of(key_tile * keys, keys), keys)
        dk_ref[0, 0, at, :] = dk_ref[0, 0, at, :] + dk * scale
        dv_ref[0, 0, at, :] = dv_ref[0, 0, at, :] + dv

    @pl.when(key_tile == last)
    def _():
        for g in range(group):
            dq_ref[0, 0, g] = (dq_acc[g] * scale).astype(dq_ref.dtype)


def _share_kernel(first_ref, q_ref, k_ref, lse_ref, bits_ref, p_ref, *, queries: int, keys: int, scale: float, heads: int):
    query_tile, key_tile, kv_head = first_ref[0] // queries + pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last = _last_tile(query_tile, queries, keys)
    group = q_ref.shape[2]

    @pl.when(kv_head == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(key_tile <= last)
    def _():
        bias, k = _bias(bits_ref, key_tile, keys), k_ref[0, 0]
        total = p_ref[0]
        for g in range(group):
            total = total + jnp.exp(_nt(q_ref[0, 0, g], k) * scale + bias - lse_ref[0, 0, :, g:g + 1])
        p_ref[0] = total

    @pl.when((kv_head == pl.num_programs(3) - 1) & (key_tile <= last))
    def _():
        p_ref[0] = p_ref[0] * (1.0 / heads)


def _specs(queries: int, keys: int, group: int, size: int, length: int):
    """The block specs of the forward and backward kernels' operands by kind, on the grid (sequence, kv head, query
    tile, key tile): a key tile past the diagonal fetches the last live one again, which is no fetch."""
    live = lambda t, c: jnp.minimum(c, _last_tile(t, queries, keys))
    return {
        "queries": pl.BlockSpec((1, 1, group, queries, size), lambda s, n, t, c: (s, n, 0, t, 0)),
        "keys": pl.BlockSpec((1, 1, keys, size), lambda s, n, t, c: (s, n, live(t, c), 0)),
        "bits": pl.BlockSpec((1, queries, LANES), lambda s, n, t, c: (s, t, live(t, c) // (SUPER // keys))),
        "lse": pl.BlockSpec((1, 1, queries, group), lambda s, n, t, c: (s, n, t, 0)),
        "whole_keys": pl.BlockSpec((1, 1, length, size), lambda s, n, t, c: (s, n, 0, 0)),
    }


def _forward(q, k, v, bits, d: Dims):
    sequences, kv_heads, group, length, size = q.shape
    queries, keys = d.tile
    spec = _specs(queries, keys, group, size, length)
    return pl.pallas_call(
        functools.partial(_forward_kernel, queries=queries, keys=keys, scale=d.scale),
        grid=(sequences, kv_heads, length // queries, length // keys),
        in_specs=[spec["queries"], spec["keys"], spec["keys"], spec["bits"]],
        out_specs=[spec["queries"], spec["lse"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct((sequences, kv_heads, length, group), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((group, queries, 1), jnp.float32), pltpu.VMEM((group, queries, 1), jnp.float32),
                        pltpu.VMEM((group, queries, size), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        interpret=d.interpret, name="sparse_core_fwd")(q, k, v, bits)


def _backward(q, k, v, bits, out, lse, sent, d: Dims):
    sequences, kv_heads, group, length, size = q.shape
    queries, keys = d.tile
    spec = _specs(queries, keys, group, size, length)
    whole = jax.ShapeDtypeStruct(k.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, queries=queries, keys=keys, scale=d.scale),
        grid=(sequences, kv_heads, length // queries, length // keys),
        in_specs=[spec["queries"], spec["keys"], spec["keys"], spec["bits"], spec["queries"], spec["lse"], spec["queries"]],
        out_specs=[spec["queries"], spec["whole_keys"], spec["whole_keys"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), whole, whole],
        scratch_shapes=[pltpu.VMEM((group, queries, size), jnp.float32), pltpu.VMEM((group, queries, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        interpret=d.interpret, name="sparse_core_bwd")(q, k, v, bits, out, lse, sent)


def heads_share(q, k, lse, bits, first, block: int, reach: int, d: Dims):
    """``p`` of the ``block`` queries from position ``first`` (a traced scalar, a whole number of blocks) against
    the keys before ``reach``: float32 (sequences, block, reach), the mean over all heads of a kept key's
    probability, exactly 0 on every other pair.  ``q``, ``k``, ``lse`` and ``bits`` whole, as :func:`core` takes
    and gives them: the kernel reads the block's rows where they lie, no slice is made."""
    sequences, kv_heads, group, length, size = q.shape
    queries, keys = d.tile
    assert block % queries == 0 and reach % keys == 0, "whole tiles (fits)"
    tile = lambda first, t: first[0] // queries + t
    live = lambda first, t, c: jnp.minimum(c, _last_tile(tile(first, t), queries, keys))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(sequences, block // queries, reach // keys, kv_heads),
        in_specs=[pl.BlockSpec((1, 1, group, queries, size), lambda s, t, c, n, first: (s, n, 0, tile(first, t), 0)),
                  pl.BlockSpec((1, 1, keys, size), lambda s, t, c, n, first: (s, n, live(first, t, c), 0)),
                  pl.BlockSpec((1, 1, queries, group), lambda s, t, c, n, first: (s, n, tile(first, t), 0)),
                  pl.BlockSpec((1, queries, LANES), lambda s, t, c, n, first: (s, tile(first, t), live(first, t, c) // (SUPER // keys)))],
        out_specs=pl.BlockSpec((1, queries, keys), lambda s, t, c, n, first: (s, t, c)))
    return pl.pallas_call(
        functools.partial(_share_kernel, queries=queries, keys=keys, scale=d.scale, heads=kv_heads * group), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((sequences, block, reach), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        interpret=d.interpret, name="sparse_core_share")(jnp.reshape(first, (1,)).astype(jnp.int32), q, k, lse, bits)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def core(q, k, v, bits, d: Dims):
    """(``out`` as ``q``, the log-sum-exp (sequences, kv heads, length, group) float32) of the masked core, head-major:
    ``q`` (sequences, kv heads, group, length, head size), ``k`` and ``v`` (sequences, kv heads, length, head size),
    all in the compute dtype; a score is ``d.scale`` times a query's product with a key, float32; ``bits`` the choice
    as planes (:func:`packed`), int32
    (sequences, length, length / 32).  The log-sum-exp is a constant to the gradient, as what reads it is
    (:func:`heads_share`); the bits have none."""
    return tuple(_forward(q, k, v, bits, d))


def _core_fwd(q, k, v, bits, d: Dims):
    out, lse = _forward(q, k, v, bits, d)
    out, lse = checkpoint_name(out, d.names[0]), checkpoint_name(lse, d.names[1])  # kept by name under rematerialisation: the forward kernel runs once a step
    return (out, lse), (q, k, v, bits, out, lse)


def _core_bwd(d: Dims, kept, sent):
    q, k, v, bits, out, lse = kept
    dq, dk, dv = _backward(q, k, v, bits, out, lse, sent[0], d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None


core.defvjp(_core_fwd, _core_bwd)
