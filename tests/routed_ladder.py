"""What the three routed architectures' tests of the expert layer's row buffer
share (``test_lfm2_moe.py``, ``test_deepseek_v2.py``, ``test_mellum2.py``): a
router and tokens that send an exact number of rows to the held experts, the
comparison of the layer at the height the ladder picks for them with the same
layer at the worst-case height alone, and the window on which each family's
``*_row_buffer_rows_per_routed_row`` reader is held to its arithmetic."""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gentun_tpu.models import lfm2_moe as M

HIGHEST = jax.default_matmul_precision("highest")


def counts_at_the_rungs(heights):
    """Every rung but the last filled to the last row, and one row more: (rows, the rung that holds them)."""
    return [(h + more, rung + more) for rung, h in enumerate(heights[:-1]) for more in (0, 1)]


def routing_of(cfg: M.Lfm2MoeConfig, tokens: int, count: int, seed: int = 4):
    """(router, x) on which exactly ``count`` of the top-k x ``tokens``
    assignments go to held experts: the first ``count % tokens`` tokens choose
    one held expert more than the others, by logits of +-3 on two columns of x
    (noise of 0.1 beside them), so every score is far from a tie and from
    saturation.  The first held experts get the rows, the last may get none."""
    experts, k, (lo, hi) = cfg.num_experts, cfg.num_experts_per_tok, cfg.held_experts
    held, others = list(range(lo, hi)), [e for e in range(experts) if not lo <= e < hi]
    few, more = count // tokens, count % tokens
    assert few + (more > 0) <= min(k, len(held)) and k - few <= len(others), (count, tokens)
    rng = np.random.default_rng(seed)
    router = 0.02 * rng.normal(size=(cfg.hidden_size, experts))
    x = rng.normal(size=(tokens, cfg.hidden_size))
    for column, n_held in enumerate((few + 1, few)):
        router[column] = -1.0
        router[column, held[:n_held] + others[:k - n_held]] = 1.0
    x[:, :2] = 0.0
    x[:more, 0] = x[more:, 1] = 3.0
    return jnp.asarray(router, jnp.float32), jnp.asarray(x, jnp.float32)


@functools.lru_cache(maxsize=None)
def _layer(cfg: M.Lfm2MoeConfig, dtype: str, row_buffer):
    def value(p, xs, bias, probe):
        out, load, use = M._moe_ffn(p, bias, xs.astype(dtype), cfg, jnp.dtype(dtype), row_buffer)
        return jnp.sum(out.astype(jnp.float32) * probe), (out, load, use)
    return jax.jit(jax.value_and_grad(jax.checkpoint(value), argnums=(0, 1), has_aux=True))


def assert_the_ladders_layer_is_the_worst_case_heights(cfg: M.Lfm2MoeConfig, experts, bias, tokens: int, count: int,
                                                        rung: int, dtype: str, tol: float) -> None:
    """Value and gradients (under ``jax.checkpoint``, as the train step runs
    the layer) of ``_moe_ffn`` on ``count`` held rows: the ladder takes
    ``rung``, drops nothing, and gives what the worst-case height alone gives."""
    heights = M._row_buffer_heights(cfg, tokens)
    router, x = routing_of(cfg, tokens, count)
    p = {**experts, "router": router}
    probe = jnp.asarray(np.random.default_rng(6).normal(size=x.shape), jnp.float32)
    bias = jnp.zeros(cfg.num_experts, jnp.float32) if bias is None else bias  # read under the bias rule alone
    with HIGHEST:
        (_, (ref_out, ref_load, ref_use)), ref_grads = _layer(cfg, dtype, heights[-1])(p, x, bias, probe)
        (_, (out, load, use)), grads = _layer(cfg, dtype, None)(p, x, bias, probe)
    lo, hi = cfg.held_experts
    assert int(load[lo:hi].sum()) == count and int(use.dropped) == 0 == int(ref_use.dropped)
    assert use.heights.tolist() == [int(r == rung) for r in range(len(heights))], (count, heights)
    assert int(use.wide) == int(rung == len(heights) - 1) and ref_use.heights.tolist() == [1]  # one height: no ladder
    np.testing.assert_array_equal(load, ref_load)
    assert float(jnp.abs(out.astype(jnp.float32)).max()) > 0
    np.testing.assert_allclose(out.astype(jnp.float32), ref_out.astype(jnp.float32), atol=tol, rtol=tol)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.abs(r).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(g, r, atol=tol * float(jnp.abs(r).max()), err_msg=jax.tree_util.keystr(path))


def assert_the_reader_divides_the_rows_run_by_the_rows_routed(reader) -> None:
    """A family's ``*_row_buffer_rows_per_routed_row`` on a window of two
    individuals: set-up's warm-up call and a span of no individual stay out, and a
    program without the attribute (the parent's) reads nothing."""
    span = lambda t, attrs: {"type": "span", "kind": "fetch", "t_wall": t, "dur_s": 0.001, "attrs": attrs}
    fetch = lambda t, rows, **attrs: span(t, {"individual": 0, "expert_rows": rows, **attrs})
    window = {"window": (10.0, 20.0)}
    records = [fetch(5.0, [[900, 100]], row_buffer_heights=[[512, 0], [2048, 9]]),  # set-up's warm-up call
               fetch(11.0, [[300, 100], [500, 300]], row_buffer_heights=[[512, 1], [1024, 1], [2048, 0]]),
               fetch(12.0, [[900, 100], [200, 0]], row_buffer_heights=[[512, 1], [1024, 0], [2048, 1]]),
               span(13.0, {"other": 1, "expert_rows": [[7]], "row_buffer_heights": [[512, 7]]})]
    assert reader.read({**window, "records": records}) == pytest.approx((2 * 512 + 1024 + 2048) / 2400)
    assert reader.read({**window, "records": [fetch(11.0, [[300, 100]], wide_buffer=0)]}) is None  # the parent's program
    assert reader.read({**window, "records": records[:1]}) is None
