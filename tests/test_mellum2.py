"""What is Mellum2-12B-A2.5B-Instruct's own among the routed family's tests (the third architecture through
``models/lfm2_moe.py``, against ``benchmark/families/mellum/reference.py``, at small sizes on the CPU); what every
architecture is held to (logits, loss and gradients a windowed layer, a full one, one and two periods; two train
steps; the shares with attention counted once; the row buffer at top-8; refusals; the manifest's readers) is in
``test_routed_family*.py`` under ``mellum2-`` ids.

Here: the window through both cores (XLA's query blocks, and the fused kernel in Pallas' interpret mode) at a
length of several windows and at one shorter than the window; the kernel's mask object against the reference's 0/1
array entry by entry; the block pairs the kernel visits against ``flops.py``'s arithmetic; rope by layer type by
hand; the grouped products' tiles at a contraction of 2304; the scopes, the spans and the labelled counter; the
configuration file, the mix and the readers; and that the two architectures that were there still build what they
built.
"""

from __future__ import annotations

import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu import deepseek_v2_genome
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry.registry import get_registry
from routed_family import HIGHEST, STD, kernel_on_the_cpu, small_kernel_blocks  # noqa: F401  (the fixtures)

A = F.ARCHS["mellum2"]
R, flops, scope_rules = A.R, A.flops, A.scope_rules
MODEL, ROPE, PERIOD = A.model, F.MELLUM_ROPE, F.MELLUM_PERIOD
NO_BIAS = jnp.zeros((8, 8), jnp.float32)  # the state's bias: zeros, never read under the ``aux_loss`` rule
_rel = F.rel


@pytest.fixture(scope="module")
def tokens():
    return A.tokens


# -- the window, through both cores ---------------------------------------------------------------------


def _core_case(length: int, seed: int = 0, sequences: int = 2, kv_heads: int = 2, group: int = 2, head: int = 16):
    rng = np.random.default_rng([seed, length])
    q = jnp.asarray(rng.normal(size=(sequences, length, kv_heads, group, head)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(sequences, length, kv_heads, head)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(sequences, length, kv_heads, head)), jnp.float32)
    return q, k, v


def _reference_core(q, k, v, scale, kind, window):
    """softmax over the keys with ``R.visible`` == 1, by the reference's explicit 0/1 array."""
    length = q.shape[1]
    i = jnp.arange(length)
    mask = R.visible(i[:, None], i[None, :], kind, {"sliding_window": window})
    scores = jnp.einsum("sqngd,sknd->sngqk", q, k) * scale
    prob = jax.nn.softmax(jnp.where(mask == 1, scores, -jnp.inf), axis=-1)
    return jnp.einsum("sngqk,sknd->sqngd", prob, v)


@pytest.mark.parametrize("length,window,block", [(96, 16, 8), (96, 16, 32), (64, 20, 16), (32, 48, 8), (24, 24, 8),
                                                 (24, 1, 8)])
def test_the_blockwise_core_under_a_window_is_the_references_masked_softmax(length, window, block):
    """Several windows long (at blocks smaller and larger than the window, and a
    window that is no whole number of blocks), shorter than the window (where it
    is the causal core), and a window of one key (a query sees itself alone)."""
    q, k, v = _core_case(length)
    with HIGHEST:
        got = M._blockwise_core(q, k, v, 0.25, block, window)
        want = _reference_core(q, k, v, 0.25, "sliding_attention", window)
        causal = M._blockwise_core(q, k, v, 0.25, block)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(causal, _reference_core(q, k, v, 0.25, "full_attention", window), atol=2e-6)
    if window >= length:
        np.testing.assert_allclose(got, causal, atol=2e-6)
    else:
        assert float(jnp.abs(got - causal).max()) > 1e-3
    if window == 1:
        np.testing.assert_allclose(got, jnp.broadcast_to(v[:, :, :, None, :], got.shape), atol=2e-6)


def test_the_blockwise_cores_cost_follows_the_window():
    """A query block is handed the keys from the last whole block its first query sees, not every earlier key."""
    q, k, v = _core_case(128)
    text = str(jax.make_jaxpr(lambda q, k, v: M._blockwise_core(q, k, v, 0.25, 16, 32))(q, k, v))
    widths = {int(n) for n in re.findall(r"f32\[2,2,2,16,(\d+)\]", text)} - {1}  # (sequences, kv heads, group, queries, keys)
    assert widths == {16, 32, 48}, widths  # 16 and 32 at the start, then the two blocks back and the block itself


@pytest.mark.parametrize("length,window,group,sequences,chunk", [(512, 128, 2, 1, 128), (256, 384, 2, 1, 0), (768, 384, 2, 2, 128),
                                                                 (640, 384, 8, 1, 128), (512, 256, 8, 1, 256), (512, 200, 2, 1, 0)])
def test_the_fused_core_under_a_window_is_the_blockwise_core_to_bfloat16(length, window, group, sequences, chunk, kernel_on_the_cpu,
                                                                          small_kernel_blocks):
    """Forward and gradients at head size 128.  Banded (``chunk``): four windows long; at a chunk that divides the
    window three times, over two sequences of two key-value heads (a chunk is a row of the kernel's outer grid: the
    order of sequences, chunks and heads is the layout's to get right) and at the published group of 8; at a window
    of one chunk.  Unbanded (0), the parent's path: shorter than the window, and a window of no whole 128 lanes."""
    assert M._kernel_chunk(length, window, group) == chunk
    q, k, v = (a.astype(jnp.bfloat16) for a in _core_case(length, sequences=sequences, kv_heads=sequences, group=group, head=128))
    scale = 1.0 / math.sqrt(128)

    def value(core):
        return lambda q, k, v: (core(q, k, v).astype(jnp.float32) ** 2).sum()

    kernel = lambda q, k, v: M._kernel_core(q, k, v, scale, window)
    blockwise = lambda q, k, v: M._blockwise_core(q, k, v, scale, 64, window)
    got = jax.jit(jax.value_and_grad(value(kernel), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(value(blockwise), argnums=(0, 1, 2)))(q, k, v)
    assert _rel(got[0], want[0]) < 2e-2
    for a, b in zip(got[1], want[1]):
        assert _rel(a, b) < 3e-2
    if 2 * window <= length:  # and it is not the causal core
        causal = jax.jit(value(lambda q, k, v: M._kernel_core(q, k, v, scale)))(q, k, v)
        assert _rel(causal, want[0]) > 5e-2
    if chunk:  # the first chunks' keys before position 0 carry no weight: the first query's output is its own value
        out = M._kernel_core(q, k, v, scale, window)
        np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                                   np.broadcast_to(np.asarray(v[:, 0, :, None, :], np.float32), out[:, 0].shape), atol=1e-2)


# -- the operator whole: what reaches the fused core, and what comes back (PR 36) ----------------------------


def _attention_case(kind: str, length: int = 256, sequences: int = 2, window: int = 96):
    """(configuration, attention weights, input) of one layer of type ``kind`` at the published head size: two
    key-value heads of two query heads each, 128 columns a head, no norm of q and k, rope by layer type."""
    cfg = M.Lfm2MoeConfig(hidden_size=64, head_dim=128, num_attention_heads=4, num_key_value_heads=2, qk_norm=False,
                          sliding_window=window, norm_eps=1e-6, seq_len=length, attn_block=64,
                          rope_parameters=F.rope_table(ROPE),
                          layer_types=("sliding_attention", "full_attention"), layer_ids=(0, 1), num_dense_layers=0)
    rng = np.random.default_rng([length, kind == "full_attention"])
    shapes = M.param_shapes(cfg)["layers"][0]["attn"]
    p = {name: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]), jnp.float32) for name, shape in shapes.items()}
    x = jnp.asarray(rng.normal(size=(sequences, length, cfg.hidden_size)), jnp.bfloat16)
    return cfg, p, x


@pytest.mark.parametrize("against", ["blockwise-bfloat16", "reference-float32"])
@pytest.mark.parametrize("kind,window", [("sliding_attention", 96), ("sliding_attention", 128), ("full_attention", 96)])
def test_attention_and_every_gradient_by_the_fused_core(kind, window, against, kernel_on_the_cpu, small_kernel_blocks):
    """``_attention`` whole (two sequences; the head-major products, rope as a product with the signed permutation
    under the layer type's frequencies and amplitude, scale and cast, the kernel under the type's mask, the output
    product over the kernel's head-major output) with the fused core interpreted, two blocks a side: in bfloat16
    against the same call by the blockwise core, in float32 against ``reference.attention`` a sequence; the output
    within two bfloat16 steps of its size, the gradients of the input and of every weight within 1% in norm (the
    bounds of ``test_deepseek_v2.py``'s latent operator).  At a window of 128 the core runs banded, two chunks a
    sequence: the q product, rope at each chunk's own positions and the output product all work on chunks as rows."""
    cfg, p, x = _attention_case(kind, window=window)
    assert M._kernel_chunk(256, cfg.window_of(kind), 2) == (128 if (kind, window) == ("sliding_attention", 128) else 0)
    dtype = jnp.bfloat16 if against == "blockwise-bfloat16" else jnp.float32
    x = x.astype(dtype)
    operator = lambda p, x: M._attention(p, x, cfg, dtype, kind)
    got = F.value_and_gradients(operator, p, x)
    if against == "blockwise-bfloat16":
        want = F.by_the_blockwise_core(operator, p, x)
    else:
        m = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=128, rope_parameters=ROPE, sliding_window=window)
        with HIGHEST:
            want = F.value_and_gradients(
                lambda p, x: jnp.stack([R.attention(p, xs, m, kind, lambda a: a) for xs in x]), p, x)
    F.assert_within_bfloat16(got, want, ("q", "k", "v", "o"))
    ref = want[0]
    other = "full_attention" if kind == "sliding_attention" else "sliding_attention"
    assert _rel(jax.jit(lambda p, x: M._attention(p, x, cfg, dtype, other))(p, x), ref) > 0.05, "the type decides"


def test_rope_on_whole_heads_is_the_sliced_rope_to_the_last_bit():
    """Rotate-half as a product with the signed permutation against the two slices and their concatenation: equal
    values, forward and cotangent, in float32 (a normed q) and from the compute dtype (a product's output), under
    plain frequencies and under YaRN's with its amplitude, at both published head sizes."""
    for head, (theta, scaling) in ((128, (5e5, None)), (128, (5e5, ROPE["full_attention"])), (64, (1e6, None))):
        rng = np.random.default_rng(head)
        for dtype in (jnp.float32, jnp.bfloat16):
            x = jnp.asarray(rng.normal(size=(2, 48, 2, 3, head)), dtype)
            g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
            want, back = jax.vjp(lambda x: M._rope(x, theta, scaling), x)
            got, back_whole = jax.vjp(lambda x: M._rope_whole_heads(x, theta, scaling), x)
            assert got.dtype == want.dtype == jnp.float32
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(np.asarray(back_whole(g)[0], np.float32), np.asarray(back(g)[0], np.float32))


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_what_reaches_the_fused_core_is_written_once_head_major_in_the_compute_dtype(kind, monkeypatch):
    """With the kernel chosen, the traced operator has no float32 array of tokens x key-value heads x head size
    elements or more outside the ``rope`` and ``core`` scopes (inside them XLA:TPU fuses norm, rope, scale and cast
    into the one pass that writes an operand: PERF.md, PR 36), no token-major array of the heads anywhere before
    the kernel (the products emit (sequences, heads, length, size)), the kernel's three operands head-major and
    in the compute dtype, and one kernel call a layer."""
    monkeypatch.setattr(M, "_use_attention_kernel", lambda length: True)
    cfg, p, x = _attention_case(kind)
    s, length, nkv, group, hd = 2, 256, 2, 2, 128
    traced = list(F.equations(jax.make_jaxpr(lambda p, x: M._attention(p, x, cfg, jnp.bfloat16, kind))(p, x).jaxpr))
    scopes = lambda scope: set(scope.split("/"))
    assert all(any(part in scopes(scope) for _, scope, _ in traced) for part in ("proj", "rope", "core"))
    arrays = [(name, scope, aval) for name, scope, avals in traced for aval in avals if hasattr(aval, "shape")]
    assert not [(n, sc, a) for n, sc, a in arrays if not {"rope", "core"} & scopes(sc)
                and a.dtype == jnp.float32 and a.size >= s * length * nkv * hd]
    products = [a for n, sc, a in arrays if "proj" in scopes(sc) and n == "transpose" and a.shape[0] == s]
    assert sorted(a.shape for a in products if a.shape[1] == nkv) == sorted(
        [(s, nkv, group, length, hd), (s, nkv, length, hd), (s, nkv, length, hd)])
    assert all(a.dtype == jnp.bfloat16 for a in products)
    assert not [(n, sc, a) for n, sc, a in arrays if a.shape in ((s, length, nkv * group * hd), (s, length, nkv * hd))]
    kernel = [avals for name, _, avals in traced if name == "custom_vjp_call"]
    assert len(kernel) == 1 and kernel[0][0].shape == (s, nkv, group, length, hd)
    operands = [a for n, sc, a in arrays if n == "transpose" and "core" in scopes(sc) and a.shape[:2] == (s, nkv)]
    assert sorted(a.shape for a in operands) == sorted(
        [(s, nkv, group, length, hd), (s, nkv, length, hd), (s, nkv, length, hd)])
    assert all(a.dtype == jnp.bfloat16 for a in operands)


@pytest.mark.parametrize("length,window,group", [(1024, 256, 2), (1024, 257, 2), (512, 1024, 2), (8192, 1024, 8), (2048, 1024, 8),
                                                 (2048, 384, 2), (512, 256, 8), (8192, 1024, 3)])
def test_the_kernels_mask_object_is_the_references_array_entry_by_entry(length, window, group):
    """What the fused kernel is handed (the library's ``LocalMask`` / ``CausalMask``, evaluated on the host: no
    TPU) against ``reference.visible``'s 0/1 array: every entry, in slices of rows at the published length.  Where
    the core runs banded the kernel is handed ONE chunk's rectangle and the segments that shut out the keys before
    position 0: laid back through the chunk layout (``F.rows_the_kernel_lets_through``) it is the same array, in the
    first ``window / chunk`` chunks, whose windows reach before the sequence, as in every other -- at the published
    shape, at a window of one chunk and of three, at a length of two chunks, at a group of 3 (whose rows are whole
    query blocks at a chunk of 128 only), and at shapes the rule refuses (a window of 257, a window past the length)."""
    causal = F.masks_handed_to_the_kernel(length, group, None)
    assert len(causal) == group
    chunk = M._kernel_chunk(length, window, group)
    assert bool(chunk) == (window % 128 == 0 and window < length)
    j = np.arange(length)[None, :]
    for first in range(0, length, 1024):
        rows = slice(first, min(first + 1024, length))
        i = np.arange(length)[rows, None]
        want = np.asarray(R.visible(i, j, "sliding_attention", {"sliding_window": window}))
        for head in sorted({0, group - 1}):
            np.testing.assert_array_equal(F.rows_the_kernel_lets_through(length, group, window, head, rows), want)
        np.testing.assert_array_equal(np.asarray(causal[0][rows, :]).astype(np.int32),
                                      np.asarray(R.visible(i, j, "full_attention", {})))
    last = F.rows_the_kernel_lets_through(length, group, window, 0, slice(length - (chunk or 1), length))[-1]
    assert int(last.sum()) == min(window, length)  # the last query sees ``window`` keys, its own the last of them


def test_a_windowed_layers_kernel_visits_fewer_block_pairs_and_flops_py_counts_the_same():
    """The kernel's own table at the published length and blocks: 36 pairs of 64 under the causal mask, and
    ``flops.block_visits`` is the same count by arithmetic.  Under the window the core runs banded at the published
    group of 8: every chunk's rows meet the chunk's own ``chunk + window`` keys and no others, so a head visits
    ``length x (chunk + window)`` score elements -- read off the banded kernel's table, never from this formula --
    which is below what the unbanded kernel's 15 pairs of 1,024 x 1,024 cost (``flops.block_visits``: still the
    count of a core that runs unbanded, and of the benchmark's arithmetic where no span says otherwise)."""
    m = {"sliding_window": 1024}
    window, causal = M._kernel_visits(8192, 1024, 256, 8), M._kernel_visits(8192, None)
    unbanded = flops.block_visits(m, "sliding_attention", 8192)
    chunk = window["chunk"]
    assert causal["pairs"] == 36 and causal == flops.block_visits(m, "full_attention", 8192) and "chunk" not in causal
    assert chunk == M._kernel_chunk(8192, 1024, 8, 256) and chunk in M._ATTN_KERNEL_CHUNKS
    assert window["elements"] == 8192 * (chunk + 1024) == window["elements_bwd"] and window["pairs"] == window["pairs_bwd"]
    assert (unbanded["pairs"], unbanded["elements"]) == (15, 15 * 1024 * 1024) and window["elements"] < unbanded["elements"]
    assert window["pairs"] == (8192 // chunk) * (8 * chunk // min(1024, 8 * chunk)) // 8  # a head's share of the grid's steps
    for kind in ("sliding_attention", "full_attention"):  # one set of blocks serves both masks where the core is unbanded
        assert flops.KERNEL_BLOCKS[kind] == (M._ATTN_KERNEL_BLOCKS["block_q"], M._ATTN_KERNEL_BLOCKS["block_kv"])
    for length, reach in ((4096, 1000), (2048, 4096), (1024, 300)):  # windows the rule refuses: the unbanded kernel's table
        visits = M._kernel_visits(length, reach, 256, 8)
        assert visits.pop("chunk") == 0 and visits == flops.block_visits({"sliding_window": reach}, "sliding_attention", length)
    mm = {"head_dim": 128, "num_attention_heads": 32, "num_key_value_heads": 4}
    assert flops.core_flops(mm, unbanded, 2, 2, 1) == 2 * 32 * 15 * 2**20 * (2 * 4 * 128 + 10 * 128)
    assert flops.core_flops(mm, unbanded, 1, 1, 0) / flops.core_flops(mm, causal, 1, 1, 0) == 15 / 36
    assert flops.core_flops(mm, window, 1, 1, 0) / flops.core_flops(mm, unbanded, 1, 1, 0) == (chunk + 1024) / (15 * 128)


# -- rope by layer type -----------------------------------------------------------------------------------


def test_rope_parameters_choose_frequencies_and_amplitude_by_layer_type():
    cfg = M.Lfm2MoeConfig(rope_parameters=F.rope_table(ROPE),
                          layer_types=tuple(PERIOD), layer_ids=(0, 1, 2, 3), sliding_window=8)
    theta, scaling = cfg.rope_of("sliding_attention")
    assert theta == 5e5 and scaling is None and cfg.window_of("sliding_attention") == 8
    theta, scaling = cfg.rope_of("full_attention")
    assert theta == 5e5 and scaling["factor"] == 16 and cfg.window_of("full_attention") is None
    assert M.yarn_amplitude(scaling) == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    assert M.yarn_amplitude({"factor": 16}) == pytest.approx(1.2772588722239782)
    assert M.yarn_amplitude({"factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707}) == 1.0  # DeepSeek-V2's form
    # by hand at head size 128: below the correction dimension of beta_fast the plain frequency, above that of
    # beta_slow the frequency over 16; d(b) = 128 ln(8192 / (2 pi b)) / (2 ln 5e5)
    low, high = math.floor(128 * math.log(8192 / (2 * math.pi * 32)) / (2 * math.log(5e5))), \
        math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5)))
    assert (low, high) == (18, 35)
    freq = M.yarn_inv_freq(128, 5e5, scaling)
    plain = 5e5 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(freq[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(freq[35:], plain[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(freq, R.rope_frequencies(128, ROPE["full_attention"])[0], rtol=1e-6)
    np.testing.assert_allclose(R.rope_frequencies(128, ROPE["sliding_attention"])[0], plain, rtol=1e-12)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 12, 3, 128)), jnp.float32)
    for kind in PERIOD[2:]:
        np.testing.assert_allclose(M._rope(x, *cfg.rope_of(kind))[0], R.rope(x[0], ROPE[kind]), atol=1e-5)
    full, plain_rope = M._rope(x, *cfg.rope_of("full_attention")), M._rope(x, *cfg.rope_of("sliding_attention"))
    np.testing.assert_allclose(np.linalg.norm(full[0, 0]), 1.2772588722239782 * np.linalg.norm(plain_rope[0, 0]), rtol=1e-6)


# -- the grouped products' tiles ---------------------------------------------------------------------------


def test_gmm_tiling_divides_a_contraction_of_2304_and_keeps_the_other_configurations_tiles():
    assert M._gmm_tiling(45056, 2304, 896) == (512, 1152, 896) and 2304 % 1152 == 0
    assert M._gmm_tiling(45056, 896, 2304) == (512, 896, 1152)  # the backward product's
    assert M._gmm_tiling(33792, 2048, 1536) == (512, 512, 512)  # LFM2's
    assert M._gmm_tiling(33792, 2048, 1408) == (512, 512, 1408) and M._gmm_tiling(33792, 1408, 2048) == (512, 1408, 512)
    assert M._gmm_tiling(96, 64, 48) == (32, 64, 48)
    assert M._gmm_tiling(512, 2048, 10944) == (512, 512, 512)  # no tile of whole lanes divides it: ragged, as it was
    for size in range(128, 8192 + 1, 128):  # whatever the width in whole lanes: a tile of up to four that divides it
        tile = M._gmm_tiling(512, size, size)[1]
        assert size % tile == 0 and tile <= 4 * M._GMM_TILING[1], (size, tile)


def test_the_grouped_product_at_2304_by_896_is_the_plain_one():
    """The published contraction and width against a loop over the groups (CPU: ``lax.ragged_dot``; the tiles
    are the TPU kernel's and are held to divide the sizes above)."""
    rng = np.random.default_rng(3)
    sizes = np.array([100, 0, 37, 63, 1, 55, 0, 128], np.int32)
    rows = jnp.asarray(rng.normal(size=(512, 2304)), jnp.float32)
    weights = jnp.asarray(rng.normal(size=(8, 2304, 896)) / 48.0, jnp.float32)
    with HIGHEST:
        got = M._grouped_matmul(rows, weights, jnp.asarray(sizes))
        want, start = np.zeros((512, 896), np.float32), 0
        for g, n in enumerate(sizes):
            want[start:start + n] = np.asarray(rows[start:start + n] @ weights[g])
            start += n
    np.testing.assert_allclose(np.asarray(got)[:start], want[:start], atol=1e-4)


# -- refusals, scopes, spans, counters --------------------------------------------------------------------


_scopes = F.scopes


def test_each_layer_type_has_its_own_scope_with_proj_rope_and_core_inside(tokens):
    cfg = A.config_of()
    w = jax.tree_util.tree_map(jnp.asarray, R.seeded_weights(MODEL, 1, STD))
    scopes = _scopes(lambda p: M.forward(cfg, p, NO_BIAS, tokens[0][:2])[0], w)
    for layer, kind in enumerate(PERIOD):
        for part in ("proj", "rope", "core"):
            assert any(s.startswith(f"layer{layer}/{kind}/{part}") for s in scopes), (layer, kind, part)
        other = "full_attention" if kind == "sliding_attention" else "sliding_attention"
        assert not any(s.startswith(f"layer{layer}/{other}") or s.startswith(f"layer{layer}/attention") for s in scopes)
    assert scope_rules.classify("jit(lm_train_step)/transpose(jvp(layer2))/sliding_attention/core/splash") == \
        ("window_core", "core")
    assert scope_rules.classify("jit(lm_train_step)/jvp(layer3)/full_attention/core/dot") == ("full_core", "core")
    assert scope_rules.classify("layer3/full_attention/rope/mul") == ("attention_proj", "rope")
    assert scope_rules.classify("layer0/sliding_attention/proj/dot_general") == ("attention_proj", "proj")
    assert scope_rules.classify("layer0/cond/branch_1_fun/moe/experts/gmm") == ("expert_mm", "experts")
    assert scope_rules.classify("layer0/aux_loss/mul") == ("moe_route", "aux_loss")
    assert scope_rules.classify("layer0/moe/router/dot") == ("moe_route", "router")
    assert scope_rules.classify("optimizer/add") == ("optimizer", "optimizer") and scope_rules.classify("") == \
        ("unattributed", "")
    assert {scope_rules.classify(s)[0] for s in scopes} <= set(scope_rules.CLASSES)


def _parent_tree_and_scopes():
    """What an LFM2 and a DeepSeek-V2 configuration built at the parent commit (PR 33): the attention leaves of
    the parameter tree and the scopes under a layer's operator."""
    return {"lfm2": ({"q", "k", "v", "o", "q_norm", "k_norm"}, {"layer1/attention"}),
            "deepseek_v2": ({"q", "kva", "kv_norm", "kvb", "o"},
                            {f"layer0/latent_attention/{p}" for p in ("down_proj", "up_proj", "rope", "core", "out_proj")})}


@pytest.mark.parametrize("arch", ["lfm2", "deepseek_v2"])
def test_the_architectures_that_were_there_build_the_tree_and_the_scopes_they_built(arch):
    """``head_dim``, the q/k norm and the mask are now stated, not implied: an LFM2 and a DeepSeek-V2
    configuration, given as before, get the implied head size, the norm, the causal mask, their one rope, and
    the scopes their benchmark families' rules read."""
    leaves, operator_scopes = _parent_tree_and_scopes()[arch]
    x = np.zeros((6, 16), np.int32)
    if arch == "lfm2":
        kw = dict(hidden_size=32, layer_types=("conv", "full_attention"), num_dense_layers=1, intermediate_size=48,
                  moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2, held_experts=(0, 2),
                  num_attention_heads=4, num_key_value_heads=2, vocab_size=64)
        key, layer = "attn", 1
    else:
        kw = dict(hidden_size=32, layer_types=("latent_attention",) * 2, num_dense_layers=1, intermediate_size=48,
                  moe_intermediate_size=24, num_experts=8, num_experts_per_tok=3, held_experts=(0, 2), n_shared_experts=2,
                  num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                  vocab_size=64, scoring_func="softmax", norm_topk_prob=False, balance_rule="aux_loss",
                  tie_word_embeddings=False,
                  rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707, mscale_all_dim=0.707,
                                    original_max_position_embeddings=8, type="yarn"))
        key, layer = "latent", 0
    programs = M.Lfm2MoeModel.compiled_programs(x, batch_sequences=2, eval_sequences=2, attn_block=8,
                                                compute_dtype="float32", **kw)
    cfg = programs.config
    assert cfg.head_dim == 8 == cfg.hidden_size // cfg.num_attention_heads and cfg.qk_norm and not cfg.typed_attention
    assert cfg.rope_parameters is None and cfg.sliding_window == 0 and cfg.window_of(cfg.layer_types[layer]) is None
    assert cfg.rope_of(cfg.layer_types[layer]) == (cfg.rope_theta, None)
    shapes = M.param_shapes(cfg)
    assert set(shapes["layers"][layer][key]) == leaves
    assert programs.kernel_layers_by_mask == (("causal", 0),) and programs.kernel_visits == ()
    params = jax.tree_util.tree_map(lambda s: jnp.zeros(s, jnp.float32), shapes, is_leaf=M._is_shape)
    bias = jnp.zeros((1, 8), jnp.float32)
    scopes = _scopes(lambda p: M.forward(cfg, p, bias, x[:2])[0], params)
    under = {s for s in scopes if re.match(rf"layer{layer}/(latent_)?attention", s)}
    depth = 3 if arch == "deepseek_v2" else 2
    assert {"/".join(s.split("/")[:depth]) for s in under if len(s.split("/")) >= depth} == operator_scopes
    assert not any("sliding_attention" in s or "full_attention" in s for s in scopes)
    top = {s.split("/")[0] for s in scopes}
    assert top == {"embed", "layer0", "layer1", "head"}, top


def test_spans_and_the_labelled_counter_split_the_kernels_layer_steps_by_mask(kernel_on_the_cpu, small_kernel_blocks):
    """One period at 512 positions with a window of 100, the kernel interpreted (the split is by layer type, a
    period has both; PR 45 cut the second period, half the test's 73 s): 3 windowed layers and 1 full one x 3
    steps an individual on the ``train`` span and on ``attention_kernel_layer_steps_total{mask}``, and the block
    pairs each mask's kernel visits as static attributes.  A window of 100 is no whole 128 lanes: the rule refuses
    it, the core runs unbanded as the parent's did (7 pairs of 128 x 128), and the span says so (a chunk of 0)."""
    m = {**MODEL, "head_dim": 128, "num_attention_heads": 2, "num_key_value_heads": 1, "sliding_window": 100}
    tok = np.random.default_rng(1).integers(0, 64, size=(6, 513)).astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    kw = A.model_kwargs(m, compute_dtype="bfloat16", attn_block=128, cache_dir=False)
    programs = M.Lfm2MoeModel.compiled_programs(x, **kw)
    assert programs.attention_kernel_layers == 4 and programs.kernel_layers_by_mask == (("causal", 1), ("window", 3))
    visits = {mask: dict(v) for mask, v in programs.kernel_visits}
    assert visits["causal"]["pairs"] == 10 and visits["window"]["pairs"] == 7  # of 16 at 4 x 4 blocks of 128
    with F.traced() as records:
        fitness = M.Lfm2MoeModel.cross_validate_population(x, y, [deepseek_v2_genome().default()], **kw)
    assert np.isfinite(fitness).all()
    trained = F.span_attrs(records, steps=3)
    assert len(trained) == 1
    attrs = trained[0]
    assert attrs["attention_kernel_layer_steps"] == 12 and attrs["attention_kernel_layer_steps_window"] == 9 \
        and attrs["attention_kernel_layer_steps_causal"] == 3
    assert attrs["attention_kernel_pairs_window"] == 7 and attrs["attention_kernel_pairs_causal"] == 10
    assert attrs["attention_kernel_elements_window"] == 7 * 128 * 128 == attrs["attention_kernel_elements_bwd_window"]
    assert attrs["attention_kernel_chunk_window"] == 0 and "attention_kernel_chunk_causal" not in attrs
    counter = get_registry().counter
    assert counter("attention_kernel_layer_steps_total", mask="window").value == 9
    assert counter("attention_kernel_layer_steps_total", mask="causal").value == 3


def test_every_matrix_starts_at_the_one_deviation_of_the_routed_family(tokens):
    """No configuration says how a run starts: norm weights at 1, every matrix at 0.02, the other routed
    configurations' start (what else was tried, and what it did to the routing, is PERF.md's, PR 34)."""
    key, h = jax.random.PRNGKey(3), jnp.asarray([1, 2], jnp.uint32)
    big = {**MODEL, "hidden_size": 128, "moe_intermediate_size": 64, "vocab_size": 64}
    params = M.Lfm2MoeModel.compiled_programs(tokens[0], **A.model_kwargs(big)).init(key, h)["params"]
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        if "norm" in path[-1].key:
            assert float(jnp.abs(a - 1).max()) == 0
        else:
            assert float(jnp.std(a)) == pytest.approx(M.INIT_STD, rel=0.1), jax.tree_util.keystr(path)


def test_on_the_cpu_every_core_falls_back_and_the_spans_say_so(tokens):
    programs = M.Lfm2MoeModel.compiled_programs(tokens[0], **A.model_kwargs())
    assert programs.attention_kernel_layers == 0 and programs.kernel_layers_by_mask == (("causal", 0), ("window", 0))
    assert programs.kernel_visits == ()


# -- the benchmark's family: configuration file, counts, readers ------------------------------------------


def _config_file():
    return F.config_file("mellum2_12b_a2p5b_ep8")


def test_the_configuration_file_holds_the_catalogs_numbers_and_the_cut_is_the_bytes_it_says():
    config = _config_file()
    published = dict(hidden_size=2304, head_dim=128, num_attention_heads=32, num_key_value_heads=4,
                     moe_intermediate_size=896, intermediate_size=7168, num_experts=64, num_experts_per_tok=8,
                     sliding_window=1024, max_position_embeddings=131072, rms_norm_eps=1e-6, max_window_layers=0)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["layer_types"] == PERIOD * 7 and config["mlp_layer_types"] == ["sparse"] * 28
    assert config["rope_parameters"] == ROPE and config["norm_topk_prob"] is True
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "train_steps", "n_sequences"}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] == 98304 and config["num_hidden_layers"] == 8
    _, family, cfg = F.published_cfg(A.family, "mellum2_12b_a2p5b_ep8")
    m = family.model_block(config)
    assert m["layer_types"] == PERIOD * 2
    need = M.training_bytes(cfg)
    assert need["params"] == 624_072_960 and need["state"] == 9_985_167_360
    assert cfg.tokens_per_step == 16384  # a mean share of 16,384 rows; 45,056 are the 2.75 shares of PR 29
    assert M._row_buffer_heights(cfg, 16384) == (20480, 45056, 131072)
    assert cfg.head_dim == 128 and not cfg.qk_norm and cfg.typed_attention and cfg.sliding_window == 1024
    shapes = M.param_shapes(cfg)
    assert shapes["layers"][0]["attn"] == {"q": (2304, 4096), "k": (2304, 512), "v": (2304, 512), "o": (4096, 2304)}
    assert shapes["layers"][3]["moe"]["w1"] == (8, 2304, 896) and shapes["head"] == (12288, 2304)
    # the executed FLOPs of a step, by flops.py: the count PERF.md's prediction rests on
    rows = 16384 * 8 / 8 * 8  # 2,048 rows a held expert, 8 experts, 8 layers
    total = flops.train_flops(m, 16384, rows, 8192)
    assert 50e12 < total < 62e12, total  # 56.7 TFLOP a step


def test_the_cell_runs_the_accepted_mix_as_it_is():
    """``lmpopeval_fresh`` unchanged: the genome's defaults first, at their own learning rate, and draws no
    hotter than the mix's cap; the pool the other ``aux_loss`` configuration's cell scores, recipe for recipe.
    The configuration file has no say in the traffic."""
    config = _config_file()
    mix = F.traffic_mix()
    pools = {}
    for name in ("mellum", "deepseek_v2"):
        with F.as_run_py_loads(name) as load:
            family = load("family")
            pools[name] = family.make_pool(4, [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
            if name == "mellum":
                small = {**config, "n_sequences": 2, "data": {**config["data"], "seq_len": 8}}
                assert family.make_inputs(small, mix, 3)["pool"] == pools[name]
    pool = pools["mellum"]
    assert "pool_log10_lr_max" not in config and mix["pool_log10_lr_max"] == -3.5
    assert len(pool) == config["population"] == 4 and pool == pools["deepseek_v2"]
    assert pool[0] == {"log10_lr": -3.5, "warmup_frac": 0.25, "weight_decay": 0.1, "beta2": 0.95, "aux_alpha": 0.001}
    assert all(r["log10_lr"] <= -3.5 and 0.0 <= r["aux_alpha"] <= 0.01 for r in pool)


@pytest.fixture()
def layer_metric():
    """A reader of ``benchmark/layer_metrics/`` by name, or a file of the family, loaded as ``run.py`` loads it."""
    with F.as_run_py_loads(A.family) as load:
        yield lambda name: load(name if name == "family" else f"layer_metrics/{name}")


def test_every_seed_gives_the_window_the_same_work_and_the_check_its_own_inputs(layer_metric):
    """The window's pool is one fixed pool, whole: the recipes from the mix's ``pool_seed``, the seed of their starting
    weights and the tokens from the configuration's ``window_seed``, so that no seed's routing gives its run more rows
    than another's (PERF.md, PR 34: the check's refusal); the order of a call is ``--seed``'s (``traffic_kinds/lmpopeval.py``), and so are the tokens
    the comparison that decides ``correct`` runs on, with its weights and batches (``correct.check_inputs``)."""
    family, mix = layer_metric("family"), F.traffic_mix()
    config = _config_file()
    small = {**config, "n_sequences": 6, "data": {**config["data"], "seq_len": 16}}
    a, b, again = (family.make_inputs(small, mix, seed) for seed in (3, 2147484001, 3))
    for key in ("x", "y"):
        assert np.array_equal(a[key], b[key]) and a[key].shape == (6, 16)
    assert a["params"] == b["params"] and a["pool"] == b["pool"] and a["model"] == b["model"]
    assert config["window_seed"] == 3400000507 and a["params"]["seed"] == config["window_seed"] % (2**31 - 1)
    assert np.array_equal(a["x"], family.markov_tokens(small["data"], small["vocab_size"], 6, 16, config["window_seed"])[:, :-1])
    assert not np.array_equal(a["check_x"], b["check_x"]) and not np.array_equal(a["check_x"], a["x"])
    assert np.array_equal(a["check_x"], again["check_x"]) and np.array_equal(a["check_y"], again["check_y"])
    assert np.array_equal(a["check_x"][:, 1:], a["check_y"][:, :-1]) and np.array_equal(a["x"][:, 1:], a["y"][:, :-1])


_span = F.span


def test_the_kernel_readers_split_the_windows_train_spans_by_mask_and_the_parent_reads_nothing(layer_metric):
    window_reader, full_reader = layer_metric("mel_window_kernel_layer_steps"), layer_metric("mel_full_kernel_layer_steps")
    train = lambda t, **attrs: _span("train", t, {"individual": 0, "steps": 8, **attrs})
    window = {"window": (10.0, 20.0)}
    records = [train(5.0, attention_kernel_layer_steps_window=0, attention_kernel_layer_steps_causal=0),  # set-up
               train(11.0, attention_kernel_layer_steps_window=48, attention_kernel_layer_steps_causal=16),
               train(12.0, attention_kernel_layer_steps_window=48, attention_kernel_layer_steps_causal=16),
               _span("train", 13.0, {"fold": 0, "attention_kernel_layer_steps_window": 99})]  # no span of this family
    assert window_reader.read({**window, "records": records}) == 48
    assert full_reader.read({**window, "records": records}) == 16
    assert window_reader.read({**window, "records": [train(11.0, attention_kernel_layer_steps=64)]}) is None  # the parent
    assert full_reader.read({**window, "records": records[:1]}) is None
