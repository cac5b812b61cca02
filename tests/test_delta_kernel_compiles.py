"""The delta rule's fused kernels compiled for a described TPU v5e, no chip attached: what Pallas' interpreter lets
pass and the chip's compiler refuses (an op Mosaic has no rule for, more fast memory than a kernel may use, a slice
off the tiling) fails here, in seconds, and not in a chip call.  Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture of this file alone: only the worker that is given the file loads the
TPU's library (on-chip-measurement guide, section 2)."""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from gentun_tpu.models import delta_kernel


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
