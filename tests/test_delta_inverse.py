"""The triangular inverse of a chunk's system as the delta kernels build it (``delta_kernel._unit_lower_inverses``: a
closed form on the blocks of a few rows, then doubling levels at their live rows or dense), outside Pallas: the
function is plain ``jax.numpy`` but for a roll, so it runs jitted on the CPU against ``solve_triangular``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gentun_tpu.models import delta_kernel

LOCKSTEP = 3  # systems advanced level by level together, as the chunks of a grid step are


def _systems(chunk: int, heads: int, decay: float, repeated: bool, seed: int):
    """``tril(beta k k' D, -1)`` of ``LOCKSTEP`` chunks of ``heads`` value heads stacked along the rows, as the kernels
    form it: unit keys, ``D`` a decay of ``decay`` a position; ``repeated``: keys in runs of 5 to 12 equal ones with
    ``beta`` within 0.002 of 1, where the system's entries sit at 1 and a solve by its powers loses every digit."""
    rng = np.random.default_rng([seed, chunk, heads])
    rows, found = heads * chunk, []
    for _ in range(LOCKSTEP):
        k = rng.normal(size=(chunk, 16))
        if repeated:
            starts = np.concatenate([[0], np.cumsum(rng.integers(5, 13, size=chunk))])
            k = k[starts[np.searchsorted(starts, np.arange(chunk), side="right") - 1]]
        k = np.tile(k / np.sqrt((k * k).sum(-1, keepdims=True)), (heads, 1))
        beta = 1.0 - rng.uniform(0.0, 0.002, size=rows) if repeated else 1 / (1 + np.exp(-rng.normal(size=rows)))
        fall = -decay * np.tile(np.cumsum(rng.uniform(0.5, 1.5, size=chunk)), heads)
        i, j = np.indices((rows, rows))
        below = (i // chunk == j // chunk) & (j < i)
        found.append(np.where(below, beta[:, None] * (k @ k.T) * np.exp(np.where(below, fall[:, None] - fall[None, :], 0.0)), 0.0))
    return [jnp.asarray(a, jnp.float32) for a in found]


CASES = {  # name: (chunk, value heads a key head, decay a position, repeated keys, product-equivalents, forms of the levels)
    "64-rows-mild": (64, 1, 0.01, False, 5.0, "closed dense halved halved halved"),
    "72-rows-three-heads-of-24-mild": (24, 3, 0.01, False, 10.0, "closed dense dense dense dense dense"),
    "128-rows-the-published-shape-mild": (64, 2, 0.01, False, 5.0, "closed dense halved halved halved"),
    "128-rows-the-published-shape-strong": (64, 2, 4.0, False, 5.0, "closed dense halved halved halved"),
    "128-rows-beta-near-1-on-repeated-keys": (64, 2, 0.001, True, 5.0, "closed dense halved halved halved"),
    "128-rows-four-heads-of-32": (32, 4, 0.01, False, 4.0, "closed dense halved halved"),
    "256-rows-four-heads-of-64-mild": (64, 4, 0.01, False, 5.0, "closed dense halved halved halved"),
    "256-rows-two-heads-of-128-repeated-keys": (128, 2, 0.001, True, 6.0, "closed dense halved halved halved halved"),
    "8-rows-one-head": (8, 1, 0.01, False, 2.0, "closed dense"),
}


@functools.lru_cache(maxsize=None)
def _inverses(chunk: int, heads: int):
    """The jitted function under test at a shape: compiled once for the cases that share it."""
    d = delta_kernel.Dims(chunk, heads, 128, 128, LOCKSTEP, True)
    return jax.jit(lambda *systems: delta_kernel._unit_lower_inverses(list(systems), delta_kernel._masks(d)))


@pytest.mark.parametrize("case", list(CASES))
def test_every_form_of_the_inverse_is_solve_triangulars(case):
    chunk, heads, decay, repeated, products, forms = CASES[case]
    assert " ".join(form for _, form in delta_kernel._inverse_forms(chunk, heads)) == forms
    assert delta_kernel.inverse_products(chunk, heads) == products
    systems = _systems(chunk, heads, decay, repeated, seed=len(case))
    with jax.default_matmul_precision("highest"):
        got = _inverses(chunk, heads)(*systems)
        eye = jnp.eye(heads * chunk, dtype=jnp.float32)
        for system, inverse in zip(systems, got):
            want = jax.scipy.linalg.solve_triangular(eye + system, eye, lower=True, unit_diagonal=True)
            assert float(jnp.abs(want - eye).max()) > 0.01  # a system that is there
            np.testing.assert_allclose(inverse, want, atol=2e-6 * float(jnp.abs(want).max()))
