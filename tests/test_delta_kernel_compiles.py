"""The delta rule's and the masked core's fused kernels, and the Nemotron-H and Keye-VL-2.0 cuts' whole train steps, compiled for a described TPU v5e, no chip attached: what Pallas' interpreter lets
pass and the chip's compiler refuses (an op Mosaic has no rule for, more fast memory than a kernel may use, a slice
off the tiling) fails here, in seconds, and not in a chip call.  Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture of this file alone: only the worker that is given the file loads the
TPU's library (on-chip-measurement guide, section 2)."""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from gentun_tpu.models import delta_kernel, sparse_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("sequences,length,key_heads,heads,dk,dv,chunk", [
    (1, 2048, 2, 2, 128, 128, 64),  # the published widths (Qwen3-Next: 16 key heads over 16,384 positions of these)
    (3, 64, 1, 2, 128, 128, 64),  # sequences of one chunk: one chunk a grid step
    (1, 256, 1, 4, 256, 256, 32),  # the widest chunk the rule admits: one chunk a grid step, by fast memory
])
def test_the_kernels_forward_and_backward_compile_for_the_described_chip(sequences, length, key_heads, heads, dk, dv, chunk,
                                                                         one_chip):
    assert delta_kernel.fits(dk, dv, chunk, heads)
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    lead = (sequences, length, key_heads)
    args = (shaped(*lead, dk), shaped(*lead, dk), shaped(*lead, heads, dv), shaped(*lead, heads), shaped(*lead, heads))
    core = lambda *a: delta_kernel.delta_core(*a, chunk)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # an entry written for a described chip cannot be read back
    try:
        forward = jax.jit(core).lower(*args).compile().as_text()
        both = jax.jit(jax.grad(lambda *a: jnp.sum(core(*a) ** 2), argnums=(0, 1, 2, 3, 4))).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert forward.count("tpu_custom_call") == 1 and "delta_core_fwd" in forward
    assert "delta_core_fwd_keep" in both and "delta_core_bwd" in both


def test_the_nemotron_h_cuts_train_step_compiles_for_the_described_chip_and_fits_it(one_chip, monkeypatch):
    """The sixth routed architecture's published cut (``benchmark/configs/nemotron3_super_120b_a12b_ep64.json``:
    731 M parameters, 11.7 GB of training state) as the chip's compiler takes it, in this file because one file
    describes the chip: the fused attention kernel at 16 query heads a key-value head, the grouped products at a
    contraction of 1,024 and 2,688 columns on each of the ladder's three heights, the Mamba-2 core as XLA's ops; and
    the step's arguments, temporaries and code together under the chip's 16 GiB (``memory_analysis``): the guard of
    the fit on every later PR, at no chip time."""
    import routed_family as F
    from gentun_tpu.models import lfm2_moe as M

    _, _, cfg = F.published_cfg("nemotron_h", "nemotron3_super_120b_a12b_ep64")
    monkeypatch.setattr(M, "_use_megablox", lambda: True)  # ``jax.default_backend()`` is the CPU here
    monkeypatch.setattr(M, "_use_attention_kernel", lambda length: M._kernel_blocks(length) is not None)
    M._programs.cache_clear()
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # an entry written for a described chip cannot be read back
    try:
        programs = M._programs(cfg)
        shaped = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        state = jax.tree_util.tree_map(shaped, jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32)))
        tokens = jax.ShapeDtypeStruct((cfg.n_sequences, cfg.seq_len), jnp.int32, sharding=one_chip)
        compiled = programs.train_step.lower(
            state, tokens, tokens, jax.ShapeDtypeStruct((cfg.train_steps, cfg.batch_sequences), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((5,), jnp.float32, sharding=one_chip), jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        M._programs.cache_clear()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert programs.attention_kernel_layers == 1 and "splash_mqa" in text and text.count("tpu_custom_call") > 100
    assert 8.7e9 < memory.argument_size_in_bytes < 8.9e9  # weights and AdamW's moments, 12 bytes a parameter, and the tokens
    assert memory.alias_size_in_bytes > 8.7e9  # donated: the state is updated in place
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes + memory.generated_code_size_in_bytes
    assert held < 14.5e9 < 16 * 2**30, memory  # the gradients are among the temporaries: 12.2 GB when this was written


@pytest.mark.parametrize("kernel", ["forward", "backward", "share"])
@pytest.mark.parametrize("tile,g", [((512, 1024), 8), ((256, 512), 8), ((512, 1024), 16)], ids=["shipped", "small", "group16"])
def test_the_masked_cores_kernels_compile_for_the_described_chip_at_the_published_shape(kernel, tile, g, one_chip):
    """Keye-VL-2.0's 4 key-value heads of 8 query heads of 128 over 16,384 positions, bfloat16, the choice as planes:
    each of the three kernels within the fast memory it asks for (the backward holds a key-value head's float32 dk and
    dv, 8 MB each and two buffers, beside its tiles), at the shipped tile and at the smallest the study timed; and 2
    key-value heads of 16 query heads, the most the rule by shape lets a grid step hold (``sparse_kernel.MAX_GROUP``)."""
    from gentun_tpu.models import lfm2_moe as M

    s, n, t, size = 1, 32 // g, 16384, 128
    d = sparse_kernel.Dims(tile, size ** -0.5, M.SPARSE_KEPT[1:])
    assert sparse_kernel.fits(t, g, size, 512, 2048, tile) and g <= sparse_kernel.MAX_GROUP
    assert tile != (512, 1024) or d == M._sparse_kernel_dims(size ** -0.5)
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q, k, bits = shaped((s, n, g, t, size)), shaped((s, n, t, size)), shaped((s, t, t // 32), jnp.int32)
    fn, args = {
        "forward": (lambda q, k, v, bits: sparse_kernel.core(q, k, v, bits, d), (q, k, k, bits)),
        "backward": (jax.grad(lambda q, k, v, bits: jnp.sum(sparse_kernel.core(q, k, v, bits, d)[0].astype(jnp.float32)), argnums=(0, 1, 2)),
                     (q, k, k, bits)),
        "share": (lambda q, k, lse, bits: sparse_kernel.heads_share(q, k, lse, bits, jnp.int32(4096), 512, 6144, d),
                  (q, k, shaped((s, n, t, g), jnp.float32), bits)),
    }[kernel]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # an entry written for a described chip cannot be read back
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    names = {"forward": ["sparse_core_fwd"], "backward": ["sparse_core_fwd", "sparse_core_bwd"], "share": ["sparse_core_share"]}[kernel]
    assert text.count("tpu_custom_call") == len(names) and all(name in text for name in names)


def test_the_keye_vl2_cuts_train_step_compiles_for_the_described_chip_and_fits_it(one_chip, monkeypatch):
    """The seventh routed architecture's published cut (``benchmark/configs/keye_vl2_30b_a3b_ep8.json``: 465 M
    parameters, 7.45 GB of training state) as the chip's compiler takes it: the selection's bisection as XLA's query
    blocks over 16,384 positions with the choice packed as planes, the masked core as the fused kernels -- the forward
    kernel ONCE a layer (its output and log-sum-exp are kept by name across the layer's rematerialisation), the
    backward kernel once, the share kernel once a group of four query blocks forward and once in the blocks' own
    rematerialisation -- the grouped products at 16 held experts on each of the ladder's heights; the step's
    arguments, temporaries and code together under the chip's 16 GiB (``memory_analysis``), and no rematerialisation
    of the compiler's own."""
    import re

    import routed_family as F
    from gentun_tpu.models import lfm2_moe as M

    _, _, cfg = F.published_cfg("keye_vl2", "keye_vl2_30b_a3b_ep8")
    monkeypatch.setattr(M, "_use_megablox", lambda: True)  # ``jax.default_backend()`` is the CPU here
    F.sparse_kernels_by_shape_alone(monkeypatch)  # ``jax.default_backend()`` is the CPU here
    M._programs.cache_clear()
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # an entry written for a described chip cannot be read back
    try:
        programs = M._programs(cfg)
        shaped = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        state = jax.tree_util.tree_map(shaped, jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32)))
        tokens = jax.ShapeDtypeStruct((cfg.n_sequences, cfg.seq_len), jnp.int32, sharding=one_chip)
        compiled = programs.train_step.lower(
            state, tokens, tokens, jax.ShapeDtypeStruct((cfg.train_steps, cfg.batch_sequences), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((5,), jnp.float32, sharding=one_chip), jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        M._programs.cache_clear()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert programs.sparse_core_layers == (("kernel", 4),) and programs.attention_kernel_layers == 0
    assert dict(programs.sparse_kernel_visits) == sparse_kernel.visits(16384, M._SPARSE_KERNEL_TILE)
    calls = lambda name: len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*' + name, text))
    assert calls("sparse_core_fwd") == 4, "the forward kernel runs once a layer and step: nothing rematerialises it"
    assert calls("sparse_core_bwd") == 4 and calls("sparse_core_share") == 4 * 8 * 2
    assert text.count("tpu_custom_call") > 50 + 72 and "splash_mqa" not in text  # the grouped products' kernels beside them
    assert 5.58e9 < memory.argument_size_in_bytes < 5.60e9  # weights and AdamW's moments, 12 bytes a parameter, and the tokens
    assert memory.alias_size_in_bytes > 5.58e9  # donated: the state is updated in place
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes + memory.generated_code_size_in_bytes
    assert held < 13.5e9 < 16 * 2**30, memory  # 11.8 GB when this was written (12.2 with XLA's query blocks): the blocks' score temporaries are gone
    assert text.count(".remat") == 0
