"""Qwen3-Next's delta rule alone (``models/lfm2_moe.py::_delta_core`` and ``models/delta_kernel.py``), on the CPU:
the chunked rule against the recurrence one position at a time (``reference.delta_rule``), at lengths that are and
are not whole chunks and under decays that an ``exp(-cumsum g)`` could not hold, and its gradient; the fused delta
kernels interpreted against XLA's ops and the recurrence; which path a backend and a shape take; and the state's
scan, which carries ``S <- A S + B`` and nothing else.  The layer, the model and the spans around the core are in
``test_qwen3_next.py``.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu.models import lfm2_moe as M
from routed_family import HIGHEST

R = F.ARCHS["qwen3_next"].R


# -- the delta rule: chunks against one position at a time --------------------------------------------------


def _delta_case(length: int, seed: int, strong: bool, sequences: int = 2, nk: int = 2, r: int = 2, dk: int = 8, dv: int = 12):
    """q, k (l2-normed), v, g, beta of ``sequences`` sequences; ``strong``: decays whose running sum over a chunk of
    16 falls far under -88, where float32's ``exp(-sum)`` is infinite."""
    rng = np.random.default_rng([seed, length])
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q = unit(rng.normal(size=(sequences, length, nk, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(sequences, length, nk, dk)))
    v = rng.normal(size=(sequences, length, nk, r, dv))
    rate = rng.uniform(5.0, 30.0, size=(nk, r)) if strong else rng.uniform(0.01, 0.5, size=(nk, r))
    g = -rate * np.log1p(np.exp(rng.normal(size=(sequences, length, nk, r))))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(sequences, length, nk, r))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _recurrence(q, k, v, g, beta):
    """``R.delta_rule`` a sequence, in the program's shapes (every value head with its key head's q and k)."""
    s, length, nk, r, dv = v.shape
    qs, ks = (jnp.repeat(a, r, axis=2) for a in (q, k))
    out = jnp.stack([R.delta_rule(qs[i], ks[i], v[i].reshape(length, nk * r, dv), g[i].reshape(length, nk * r),
                                  beta[i].reshape(length, nk * r)) for i in range(s)])
    return out.reshape(v.shape)


@pytest.mark.parametrize("strong", [False, True], ids=["mild-decays", "decays-exp-minus-sum-cannot-hold"])
@pytest.mark.parametrize("length,chunk", [(64, 16), (57, 16), (16, 16), (5, 16), (33, 8)])
def test_the_chunked_delta_rule_is_the_recurrence_one_position_at_a_time(length, chunk, strong):
    q, k, v, g, beta = _delta_case(length, 3, strong)
    if strong and length >= chunk:
        falls = np.asarray(jnp.cumsum(g[:, :chunk], axis=1))
        with np.errstate(over="ignore"):
            assert falls.min() < -100 and not np.isfinite(np.exp(-falls.astype(np.float32))).all()
    with HIGHEST:
        got = jax.jit(lambda *a: M._delta_core(*a, chunk))(q, k, v, g, beta)
        want = jax.jit(_recurrence)(q, k, v, g, beta)
    assert np.isfinite(np.asarray(got)).all() and float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()) + 1e-6)


@pytest.mark.parametrize("strong", [False, True], ids=["mild-decays", "strong-decays"])
@pytest.mark.parametrize("length,chunk", [(48, 16), (41, 16)])
def test_the_chunked_delta_rules_gradient_is_jax_grad_of_the_recurrence(length, chunk, strong):
    args = _delta_case(length, 4, strong)
    probe = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)
    value = lambda core: (lambda *a: jnp.sum(core(*a) * probe))
    with HIGHEST:
        got = jax.jit(jax.grad(value(lambda *a: M._delta_core(*a, chunk)), argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(value(_recurrence), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        scale = float(jnp.abs(b).max())
        assert scale > 0 and np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, err_msg=name)


def test_the_state_crosses_chunk_boundaries():
    """The output after a boundary depends on what was written before it (a state reset a chunk would not)."""
    q, k, v, g, beta = _delta_case(32, 6, strong=False)
    with HIGHEST:
        base = M._delta_core(q, k, v, g, beta, 8)
        moved = M._delta_core(q, k, v.at[:, 3].add(1.0), g, beta, 8)
    assert float(jnp.abs(moved - base)[:, 8:].max()) > 1e-3 and float(jnp.abs(moved - base)[:, :3].max()) == 0


# -- the delta rule's core as fused kernels (interpreted on the CPU) against XLA's ops and the recurrence ------------


def _repeated_keys_case(length: int, seed: int):
    """``beta`` within 0.002 of 1 on keys that repeat in runs of 5 to 12 positions under a decay of ~0.001 a
    position: the chunk's system has entries at 1, where a solve by powers of the system loses every digit."""
    q, k, v, g, beta = _delta_case(length, seed, strong=False)
    rng = np.random.default_rng([seed, length, 1])
    starts = np.concatenate([[0], np.cumsum(rng.integers(5, 13, size=length))])
    run_of = np.searchsorted(starts, np.arange(length), side="right") - 1
    k = k[:, starts[run_of]]
    beta = jnp.asarray(1.0 - rng.uniform(0.0, 0.002, size=beta.shape), jnp.float32)
    return q, k, v, 0.002 * g, beta


KERNEL_CASES = {  # name: (operands, chunk)
    "mild-decays-state-over-four-boundaries": lambda: (_delta_case(80, 11, strong=False), 16),
    "strong-decays": lambda: (_delta_case(64, 12, strong=True), 16),
    "beta-near-1-on-repeated-keys": lambda: (_repeated_keys_case(64, 13), 32),
    "no-whole-number-of-chunks": lambda: (_delta_case(41, 14, strong=False), 16),
    "one-value-head-a-key-head": lambda: (_delta_case(40, 15, strong=False, r=1), 8),
    "three-value-heads-chunks-of-24": lambda: (_delta_case(72, 16, strong=False, sequences=1, r=3), 24),
}


@pytest.fixture(scope="module")
def kernel_readings():
    """Output and every gradient of a case by the kernels, by XLA's ops and by the recurrence: computed once a case."""
    from gentun_tpu.models import delta_kernel

    done = {}

    def readings(case):
        if case not in done:
            args, chunk = KERNEL_CASES[case]()
            probe = jnp.asarray(np.random.default_rng(2).normal(size=args[2].shape), jnp.float32)
            cores = {"kernel": lambda *a: delta_kernel.delta_core(*a, chunk, interpret=True),
                     "xla": lambda *a: M._delta_core_xla(*a, chunk), "recurrence": _recurrence}
            with HIGHEST:
                done[case] = {name: jax.jit(lambda *a, core=core: (core(*a), jax.grad(
                    lambda *b: jnp.sum(core(*b) * probe), argnums=(0, 1, 2, 3, 4))(*a)))(*args) for name, core in cores.items()}
        return done[case]

    return readings


@pytest.mark.parametrize("oracle", ["xla", "recurrence"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_fused_delta_kernels_are_the_chunked_rule_forward_and_every_gradient(case, oracle, kernel_readings):
    (got, got_grads), (want, want_grads) = kernel_readings(case)["kernel"], kernel_readings(case)[oracle]
    assert np.isfinite(np.asarray(got)).all() and float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()) + 1e-6)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got_grads, want_grads):
        scale = float(jnp.abs(b).max())
        assert scale > 0 and np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, err_msg=name)


def test_the_fused_delta_kernels_state_crosses_chunk_boundaries_and_grid_steps():
    """A value written at position 3 moves the outputs of later chunks and of the next grid step (chunks of 8, four a
    grid step), none before it; and without a backward pass to follow the forward kernel writes no state."""
    from gentun_tpu.models import delta_kernel

    q, k, v, g, beta = _delta_case(64, 6, strong=False)
    core = jax.jit(lambda *a: delta_kernel.delta_core(*a, 8, interpret=True))
    base, moved = core(q, k, v, g, beta), core(q, k, v.at[:, 3].add(1.0), g, beta)
    assert delta_kernel.MAX_STEPS == 4 and float(jnp.abs(moved - base)[:, 32:].max()) > 1e-3
    assert float(jnp.abs(moved - base)[:, 8:32].max()) > 1e-3 and float(jnp.abs(moved - base)[:, :3].max()) == 0
    def written(jaxpr):  # the arrays each kernel call of a program writes, nested calls' included
        return [n for eqn in jaxpr.eqns for n in ([len(eqn.outvars)] if eqn.primitive.name == "pallas_call" else
                                                  [m for sub in jax.core.jaxprs_in_params(eqn.params) for m in written(sub)])]

    forward = lambda *a: delta_kernel.delta_core(*a, 8, interpret=True)
    assert written(jax.make_jaxpr(forward)(q, k, v, g, beta).jaxpr) == [1]
    both = jax.grad(lambda *a: jnp.sum(forward(*a)), argnums=(0, 1, 2, 3, 4))
    assert written(jax.make_jaxpr(both)(q, k, v, g, beta).jaxpr) == [2, 4]  # o and the states; dq, dk, dv and the gates'


@pytest.mark.parametrize("backend,dk,dv,chunk,heads,kernel", [
    ("cpu", 128, 128, 64, 2, False), ("tpu", 16, 24, 16, 2, False), ("tpu", 128, 128, 60, 2, False),
    ("tpu", 128, 128, 64, 2, True), ("tpu", 256, 128, 8, 1, True),
    ("tpu", 256, 256, 32, 4, True), ("tpu", 256, 256, 64, 8, False), ("tpu", 512, 512, 64, 4, False)])  # what fast memory holds
def test_the_delta_cores_path_follows_the_backend_and_the_shape(backend, dk, dv, chunk, heads, kernel, monkeypatch):
    """The CPU and the rehearsal's widths trace XLA's ops, the published widths on a TPU backend the kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert M._use_delta_kernel(dk, dv, chunk, heads) is kernel
    length = 2 * chunk
    shapes = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in
              ((1, length, 1, dk), (1, length, 1, dk), (1, length, 1, heads, dv), (1, length, 1, heads), (1, length, 1, heads))]
    traced = str(jax.make_jaxpr(lambda *a: M._delta_core(*a, chunk))(*shapes))
    assert ("pallas_call" in traced) is kernel and ("triangular_solve" in traced) is not kernel


# -- the delta rule's scan: the state's own recurrence and nothing else ---------------------------------------------


@pytest.mark.parametrize("steps,lead,n,m", [(5, (2,), 4, 6), (3, (2, 1, 2), 8, 5)])
def test_the_affine_scans_rule_is_jax_grad_of_a_loop_over_the_steps(steps, lead, n, m):
    """A cotangent on every emitted state, not only the last: ``da`` and ``db`` against a plain Python loop."""
    rng = np.random.default_rng([steps, n, m])
    a = jnp.asarray(0.5 * rng.normal(size=(steps, *lead, n, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(steps, *lead, n, m)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=b.shape), jnp.float32)

    def loop(a, b):
        state, entered = jnp.zeros_like(b[0]), []
        for a_i, b_i in zip(a, b):
            entered.append(state)
            state = jnp.matmul(a_i, state) + b_i
        return jnp.stack(entered)

    with HIGHEST:
        np.testing.assert_allclose(M._affine_scan(a, b), loop(a, b), rtol=1e-5, atol=1e-5)
        got = jax.jit(jax.grad(lambda a, b: jnp.sum(M._affine_scan(a, b) * probe), argnums=(0, 1)))(a, b)
        want = jax.grad(lambda a, b: jnp.sum(loop(a, b) * probe), argnums=(0, 1))(a, b)
    for name, x, y in zip(("da", "db"), got, want):
        assert float(jnp.abs(y[:-1]).max()) > 1e-2 and float(jnp.abs(x[-1]).max()) == 0, name  # nothing reads the last step
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5 * float(jnp.abs(y).max()), err_msg=name)


def _scan_bodies(jaxpr):
    """The primitives of every ``scan``'s body in ``jaxpr``, nested calls' included: one list a scan."""
    def names(jaxpr):
        return [name for eqn in jaxpr.eqns
                for name in [eqn.primitive.name] + [n for sub in jax.core.jaxprs_in_params(eqn.params) for n in names(sub)]]

    found = []
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "scan":
            found.extend(names(sub) for sub in subs)
        else:
            found.extend(body for sub in subs for body in _scan_bodies(sub))
    return found


@pytest.mark.parametrize("traced,scans", [("forward", 1), ("gradient", 2)])
def test_a_chunk_step_holds_the_chain_products_and_no_exp(traced, scans):
    """Work that slides back into the loop (an output product, a gate's ``exp``) fails here."""
    args = _delta_case(41, 5, strong=False)
    core = lambda *a: M._delta_core(*a, 16)
    fn = core if traced == "forward" else jax.grad(lambda *a: jnp.sum(core(*a) ** 2), argnums=(0, 1, 2, 3, 4))
    bodies = _scan_bodies(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(bodies) == scans
    for body in bodies:
        assert body.count("dot_general") == M.LINEAR_CORE_CHAIN_PRODUCTS, body
        assert "exp" not in body and "exp2" not in body and not any("checkpoint" in name or "remat" in name for name in body), body


@pytest.mark.parametrize("strong", [False, True], ids=["mild-decays", "decays-exp-minus-sum-cannot-hold"])
def test_the_batched_passes_hand_the_scan_finite_operands_and_its_states_are_the_recurrences(strong, monkeypatch):
    """``A``, ``B`` and the stacked states under decays whose ``exp(-G)`` float32 cannot hold; the state that
    enters chunk ``i`` is the recurrence's after ``16 i`` positions; every gradient stays finite."""
    chunk, length = 16, 41
    q, k, v, g, beta = args = _delta_case(length, 7, strong)
    handed = {}
    scan = M._affine_scan

    def watched(a, b):
        handed["a"], handed["b"], handed["entered"] = a, b, scan(a, b)
        return handed["entered"]

    monkeypatch.setattr(M, "_affine_scan", watched)
    with HIGHEST:
        M._delta_core(*args, chunk)
    monkeypatch.undo()
    a, b, entered = (np.asarray(handed[name]) for name in ("a", "b", "entered"))
    assert a.shape == (3, 2, 2, 2, 8, 8) and b.shape == entered.shape == (3, 2, 2, 2, 8, 12)
    assert np.isfinite(a).all() and np.isfinite(b).all() and np.abs(b).max() > 1e-3
    state = np.zeros((2, 2, 2, 8, 12))  # (sequences, key heads, value heads a key head, key size, value size), float64
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in args)
    for t in range(2 * chunk + 1):
        if t % chunk == 0:
            np.testing.assert_allclose(entered[t // chunk], state, atol=2e-5 * max(np.abs(state).max(), 1e-2))
        state = np.exp(g[:, t])[..., None, None] * state
        wrote = beta[:, t][..., None] * (v[:, t] - np.einsum("snrde,snd->snre", state, k[:, t]))
        state = state + np.einsum("snd,snre->snrde", k[:, t], wrote)
    with HIGHEST:
        grads = jax.grad(lambda *x: jnp.sum(M._delta_core(*x, chunk) ** 2), argnums=(0, 1, 2, 3, 4))(*args)
    assert all(np.isfinite(np.asarray(d)).all() for d in grads)
