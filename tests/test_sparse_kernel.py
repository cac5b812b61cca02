"""The masked core's fused kernels (``models/sparse_kernel.py``) interpreted on the CPU and held to XLA's query blocks
(``lfm2_moe._sparse_core``) on the same choice: ``out``, the log-sum-exp, dq, dk, dv and a block's ``p``, in float32
and bfloat16, at two super-tiles of the bits (8,192 positions) and 2 and 8 query heads a key-value head.  The choice is
the selection's own (queries with fewer keys than ``topk``, which keep them all; ties at the threshold, kept) with rows
forced to what a kernel could get wrong: queries whose first kept key lies in their last tile, and a tile in which no
query keeps a key.  Then the bits' layout, the rule by shape, and the table the spans read."""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.models import sparse_kernel as K

LENGTH, SIZE, TOP, BLOCK = 2 * K.SUPER, 128, 1024, 512
TILE, SCALE = (256, 512), SIZE ** -0.5
DIMS = K.Dims(TILE, SCALE, ("out", "lse"), True)
#: Queries that keep nothing before their last key tile, and the (query tile, key tile) that no query keeps a key of.
LATE, EMPTY = slice(5000, 5100), (slice(6144, 6400), slice(1024, 1536))
SHARE_BLOCKS = (0, 4608, 6144, 7680)  # first positions of the query blocks whose ``p`` is compared
CASES = [("float32", 2), ("float32", 8), ("bfloat16", 2), ("bfloat16", 8)]
#: A quantity's bound as a share of the oracle's largest entry, by the operands' type.
BOUNDS = {"float32": dict(out=2e-5, lse=2e-6, dq=1e-4, dk=1e-4, dv=1e-4, p=2e-5),
          "bfloat16": dict(out=2e-2, lse=2e-2, dq=4e-2, dk=4e-2, dv=4e-2, p=3e-2)}


@functools.lru_cache(maxsize=None)
def choice():
    """(kept, bool (1, LENGTH, LENGTH); the indexer's operands it was selected from): the selection's own, on keys of
    which some are copies of others (their scores tie), then the forced rows."""
    rng = np.random.default_rng(0)
    q_idx = jnp.asarray(rng.normal(size=(1, LENGTH, 2, 8)), jnp.float32)
    k_idx = rng.normal(size=(1, LENGTH, 8)).astype(np.float32)
    k_idx[0, 1::7] = k_idx[0, 0:-1:7][: len(k_idx[0, 1::7])]  # every seventh key twice: ties, at the threshold among them
    w_idx = jnp.asarray(rng.uniform(0.1, 1.0, size=(1, LENGTH, 2)), jnp.float32)
    k_idx = jnp.asarray(k_idx)
    kept = np.array(M._unpacked(jax.jit(lambda *a: M._sparse_selection(*a, TOP, BLOCK))(q_idx, k_idx, w_idx), LENGTH))
    counts = kept[0].sum(axis=1)
    assert (counts[:TOP] == np.arange(1, TOP + 1)).all(), "a query with no more than topk keys keeps them all"
    assert (counts[TOP:] >= TOP).all() and (counts[TOP:] > TOP).any(), "ties at the threshold are kept"
    for t in range(LATE.start, LATE.stop):
        kept[0, t] = False
        kept[0, t, t - 3:t + 1] = True
    kept[0][EMPTY] = False
    assert kept[0].any(axis=1).all() and not np.triu(kept[0], 1).any()
    return kept, (q_idx, k_idx, w_idx)


@functools.lru_cache(maxsize=None)
def quantities(dtype: str, group: int):
    """Every compared quantity of one case, the kernels' and the oracle's: {name: (got, want)}."""
    kept, (q_idx, k_idx, w_idx) = choice()
    rng = np.random.default_rng(group)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(1, LENGTH, 1, group, SIZE)), dt)
    k, v = (jnp.asarray(rng.normal(size=(1, LENGTH, 1, SIZE)), dt) for _ in range(2))
    sent = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    planes = K.packed(jnp.asarray(kept))

    def oracle(q, k, v):  # XLA's query blocks, on the same choice and the same unscaled operands
        out, _, _ = M._sparse_core(q, k, v, q_idx, k_idx, w_idx, planes, SCALE, BLOCK)
        return out

    def kernels(q, k, v):  # the operands head-major, as ``_sparse_kernel_core`` hands them over; the scale is the kernels' to apply
        heads_q = q.transpose(0, 2, 3, 1, 4)
        out, lse = K.core(heads_q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), planes, DIMS)
        return out.transpose(0, 3, 1, 2, 4), (heads_q, lse)

    def written_out(q, k):  # what the blocks hold and never return: every head's log-sum-exp, and the heads' share
        scores = jnp.einsum("sqngd,sknd->sngqk", q, k, preferred_element_type=jnp.float32) * SCALE
        scores = jnp.where(jnp.asarray(kept)[:, None, None], scores, -jnp.inf)
        return jax.nn.logsumexp(scores, axis=-1), M._heads_share(jax.nn.softmax(scores, axis=-1))

    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * sent)
    (out, (heads_q, lse)), want_out = jax.jit(kernels)(q, k, v), jax.jit(oracle)(q, k, v)
    got_grads = jax.jit(jax.grad(loss(lambda *a: kernels(*a)[0]), argnums=(0, 1, 2)))(q, k, v)
    want_grads = jax.jit(jax.grad(loss(oracle), argnums=(0, 1, 2)))(q, k, v)
    want_lse, want_p = jax.jit(written_out)(q, k)
    share = jax.jit(lambda first: K.heads_share(heads_q, k.transpose(0, 2, 1, 3), lse, planes, first, BLOCK, LENGTH, DIMS))
    got_p = jnp.concatenate([share(jnp.int32(first)) for first in SHARE_BLOCKS], axis=1)
    rows = np.concatenate([np.arange(first, first + BLOCK) for first in SHARE_BLOCKS])
    found = {"out": (out, want_out), "lse": (jnp.moveaxis(lse, -1, 2), want_lse), "p": (got_p, want_p[:, rows]),
             **{f"d{name}": pair for name, pair in zip("qkv", zip(got_grads, want_grads))}}
    return {name: (np.asarray(a, np.float32), np.asarray(b, np.float32)) for name, (a, b) in found.items()}, kept[:, rows]


@pytest.mark.parametrize("dtype,group", CASES)
@pytest.mark.parametrize("name", ["out", "lse", "dq", "dk", "dv", "p"])
def test_a_quantity_of_the_kernels_is_the_query_blocks(name, dtype, group):
    found, kept_rows = quantities(dtype, group)
    got, want = found[name]
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(want).max() > 1e-3
    assert np.abs(got - want).max() <= BOUNDS[dtype][name] * np.abs(want).max(), (np.abs(got - want).max(), np.abs(want).max())
    if name == "p":  # every masked entry weighs exactly 0, and a query's kept keys share its whole weight
        assert not got[~kept_rows].any()
        np.testing.assert_allclose(got.sum(axis=-1), 1.0, atol=2e-2 if dtype == "bfloat16" else 1e-5)
    if name == "dk":  # the empty tile's keys still got their gradient from every other query tile
        assert np.abs(got[0, EMPTY[1]]).max() > 0


def test_the_late_rows_and_the_empty_tile_come_out_exact():
    """Rows whose first kept key arrives in their last tile carry garbage until then, which that key wipes: their
    output is the softmax over their four keys, to float32's rounding; rows of the empty tile lose nothing."""
    found, _ = quantities("float32", 2)
    got, want = found["out"]
    assert np.abs(got[0, LATE] - want[0, LATE]).max() < 5e-6 and np.abs(want[0, LATE]).max() > 0.1
    assert np.abs(got[0, EMPTY[0]] - want[0, EMPTY[0]]).max() < 2e-5


@pytest.mark.parametrize("keys", [LENGTH, K.SUPER, 2048, 96, 5000])
def test_the_bits_round_trip_as_planes(keys):
    kept = jnp.asarray(np.random.default_rng(keys).random((2, 24, keys)) < 0.3)
    planes = M._packed(kept)
    assert planes.dtype == jnp.int32 and planes.shape == (2, 24, -(-keys // K.SUPER) * 128) and planes.shape[-1] == K.words(keys)
    assert np.array_equal(M._unpacked(planes, keys), kept)
    # bit b of word l of a super-tile is its key 128 b + l
    word = np.asarray(planes)[1, 3].view(np.uint32)
    for key in (0, 1, 127, 128, keys // 2, keys - 1):
        s, within = divmod(key, K.SUPER)
        assert bool(word[s * 128 + within % 128] >> np.uint32(within // 128) & 1) == bool(kept[1, 3, key])
    assert np.array_equal(M._unpacked(planes, min(keys, 90)), kept[..., :90])
    # a key a byte's bit in key order, the layout the choice had before the kernels: the same set either way
    octets = np.packbits(np.asarray(kept), axis=-1, bitorder="little")
    assert np.array_equal(np.unpackbits(octets, axis=-1, count=keys, bitorder="little").astype(bool), M._unpacked(planes, keys))


@pytest.mark.parametrize("length", [LENGTH, 96])
def test_the_selection_hands_on_its_choice_as_planes(length):
    """The one layout on every backend: whole super-tiles of words a query, the same bytes a query as a bit a key at
    the kernels' lengths; no key ahead of its query, and every query keeps its own position's candidates."""
    _, (q_idx, k_idx, w_idx) = choice()
    top, block = (TOP, BLOCK) if length == LENGTH else (16, 8)
    planes = jax.jit(lambda *a: M._sparse_selection(*a, top, block))(q_idx[:, :length], k_idx[:, :length], w_idx[:, :length])
    assert planes.dtype == jnp.int32 and planes.shape == (1, length, K.words(length))
    assert length % K.SUPER or planes.size * 4 == length * length // 8
    kept = np.asarray(M._unpacked(planes, length))[0]
    assert not np.triu(kept, 1).any() and (kept.sum(axis=1) >= np.minimum(np.arange(length) + 1, top)).all()
    assert np.array_equal(K.packed(jnp.asarray(kept))[None], planes), "the padding past the last key is zeros"


@pytest.mark.parametrize("length,group,size,block,takes", [
    (16384, 8, 128, 512, True),  # the published shape
    (8192, 2, 128, 512, True), (4096, 1, 256, 512, True),
    (2048, 8, 128, 512, False),  # no whole super-tile of the bits
    (96, 2, 16, 8, False), (12288, 8, 128, 512, True),
    (16384, 8, 64, 512, False),  # a head of half the lanes
    (16384, 8, 128, 128, False),  # a query block of the loss pass that is no whole query tile
    (32768, 8, 128, 512, False),  # a key-value head's dk and dv past fast memory
    (16384, 16, 128, 512, True), (16384, 32, 128, 512, False),  # more query heads a key-value head than a grid step holds
])
def test_the_rule_by_shape_takes_whole_tiles_of_whole_lanes_on_a_tpu_alone(length, group, size, block, takes, monkeypatch):
    assert not M._use_sparse_kernel(length, group, size, block), "the CPU runs XLA's query blocks"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert M._use_sparse_kernel(length, group, size, block) == takes


@pytest.mark.parametrize("tile,reach,takes", [((512, 1024), 2048, True), ((512, 2048), 2048, True), ((512, 4096), 4096, True),
                                              ((512, 4096), 2048, False)])  # the first group's grid would be 0 key tiles
def test_the_rule_wants_the_keys_a_group_of_the_loss_pass_reaches_in_whole_key_tiles(tile, reach, takes):
    assert K.fits(16384, 8, 128, 512, reach, tile) == takes


def test_the_table_the_spans_read_is_the_kernels_own():
    """Every (query tile, key tile) up to the diagonal, none skipped for being empty: the causal area in whole tiles."""
    assert K.visits(16384, TILE) == {"tiles": 1056, "elements": 138_412_032, "elements_bwd": 138_412_032}
    assert K.visits(16384, (512, 1024)) == {"tiles": 272, "elements": 272 * 512 * 1024, "elements_bwd": 272 * 512 * 1024}
    assert K.visits(16384, (512, 1024)) == K.visits(16384, M._SPARSE_KERNEL_TILE)
    assert [K._last_tile(t, 256, 512) for t in range(5)] == [0, 0, 1, 1, 2] and K._last_tile(3, 512, 256) == 7
    causal = 16384 * 16385 // 2
    assert causal < K.visits(16384, TILE)["elements"] < M._sparse_visits(16384, 512)["elements"]


def test_the_share_kernel_refuses_a_reach_of_no_whole_key_tile():
    """``reach // keys`` key tiles is the grid: a remainder would come out unwritten, so it is an error, not a result."""
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    with pytest.raises(AssertionError, match="whole tiles"):
        K.heads_share(z(1, 1, 2, LENGTH, SIZE), z(1, 1, LENGTH, SIZE), z(1, 1, LENGTH, 2), jnp.zeros((1, LENGTH, K.words(LENGTH)), jnp.int32),
                      jnp.int32(0), 512, 768, DIMS)
