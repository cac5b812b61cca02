"""The four routed architectures without a windowed layer trace the programs they traced before the windowed core
ran banded (PR 48): ``lm_init``, ``lm_train_step`` and ``lm_eval`` of each published cut, with the fused kernels
chosen as a TPU would choose them, as jaxpr text with file names taken out, held against hashes recorded from the
parent commit (719fbcb) by the same function.  A causal call of ``_kernel_core`` is held the same way.

What a failure means: an edit reached a program it had no business with (a reordered operation is enough).  Where
the edit is meant to, record the new hashes with ``python tests/test_windowless_programs.py`` and say so in
``CHANGES.md``."""

from __future__ import annotations

import hashlib
import re

import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu.models import delta_kernel
from gentun_tpu.models import lfm2_moe as M

CELLS = {"lfm2_moe": "lfm2_24b_a2b_ep8", "deepseek_v2": "deepseek_v2_lite_ep8", "qwen3_next": "qwen3_next_80b_a3b_ep16",
         "nemotron_h": "nemotron3_super_120b_a12b_ep64"}
#: (sha256 of the text, first 16 digits; its length) as the parent commit traced them
PARENT = {
    "lfm2_moe.lm_init": ("dcf42286df297fc7", 77813), "lfm2_moe.lm_train_step": ("0c539a48d2bc62ad", 2433819),
    "lfm2_moe.lm_eval": ("22ab53470e0dc8f1", 1055732),
    "deepseek_v2.lm_init": ("f7ea53c30748d3f9", 95402), "deepseek_v2.lm_train_step": ("506881e638426f02", 2568329),
    "deepseek_v2.lm_eval": ("9d7a224a164ced4e", 1093395),
    "qwen3_next.lm_init": ("af6d58095e2fedb4", 82863), "qwen3_next.lm_train_step": ("c093cfd683fb1b47", 3629427),
    "qwen3_next.lm_eval": ("e4e3f7b434d141bd", 1578295),
    "nemotron_h.lm_init": ("1c45d7b2a4ad1b72", 105088), "nemotron_h.lm_train_step": ("bf701b81b95bff27", 2603846),
    "nemotron_h.lm_eval": ("6592e2598ccc3e0b", 862242),
    "causal_kernel_core": ("cdc51116fdceef6d", 15515),
}


def fingerprint(text: str):
    text = re.sub(r"/[\w/.\-]+\.py(:\d+)?", "<file>", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text)


def traced(family: str):
    """The three programs of ``family``'s published cut as jaxpr text, every fused kernel chosen by its rule of shape."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(M, "_use_megablox", lambda: True)
        mp.setattr(M, "_use_attention_kernel", lambda length: M._kernel_blocks(length) is not None)
        mp.setattr(M, "_use_delta_kernel", delta_kernel.fits)
        _, _, cfg = F.published_cfg(family, CELLS[family])
        assert "sliding_attention" not in cfg.layer_types and any(kind in M.ATTENTION_KINDS for kind in cfg.layer_types)
        M._programs.cache_clear()
        try:
            programs = M._programs(cfg)
            key, hashes = jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32)
            state = jax.eval_shape(programs.init, key, hashes)
            shaped = jax.ShapeDtypeStruct
            tokens = shaped((cfg.n_sequences, cfg.seq_len), jnp.int32)
            return {
                "lm_init": str(jax.make_jaxpr(programs.init)(key, hashes)),
                "lm_train_step": str(jax.make_jaxpr(programs.train_step)(
                    state, tokens, tokens, shaped((cfg.train_steps, cfg.batch_sequences), jnp.int32), shaped((5,), jnp.float32),
                    shaped((), jnp.int32))),
                "lm_eval": str(jax.make_jaxpr(programs.eval)(state["params"], state["bias"], tokens, tokens,
                                                             shaped((cfg.batch_sequences,), jnp.int32))),
            }
        finally:
            M._programs.cache_clear()


def causal_kernel_core() -> str:
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    return str(jax.make_jaxpr(lambda q, k, v: M._kernel_core(q, k, v, 0.125))(
        shaped(2, 2048, 2, 4, 128), shaped(2, 2048, 2, 128), shaped(2, 2048, 2, 128)))


@pytest.fixture(scope="module")
def texts():
    kept = {}
    return lambda family: kept.setdefault(family, traced(family))


@pytest.mark.parametrize("program", ["lm_init", "lm_train_step", "lm_eval"])
@pytest.mark.parametrize("family", sorted(CELLS))
def test_an_architecture_without_a_windowed_layer_traces_the_parents_program(family, program, texts):
    assert fingerprint(texts(family)[program]) == PARENT[f"{family}.{program}"]


def test_a_causal_call_of_the_fused_core_traces_the_parents_program():
    text = causal_kernel_core()
    assert "splash" in text and fingerprint(text) == PARENT["causal_kernel_core"]


if __name__ == "__main__":
    for family in sorted(CELLS):
        for program, text in traced(family).items():
            print(f'    "{family}.{program}": {fingerprint(text)},')
    print(f'    "causal_kernel_core": {fingerprint(causal_kernel_core())},')
