"""The routed family's fourth architecture (Qwen3-Next-80B-A3B-Instruct through
``models/lfm2_moe.py``) against its plain reference
(``benchmark/families/qwen3_next/reference.py``), at small sizes on the CPU.

System and reference are compared in float32 on seeded weights: per layer kind
(a Gated DeltaNet layer, a gated attention layer, one period) on logits, loss
(with the balance term) and every gradient; over two train steps; the share
test ties the expert layer's cut to the uncut layer with the mixer, the shared
expert and its gate counted once.  Then what is the architecture's own: the
chunked delta rule against the recurrence one position at a time, at lengths
that are and are not whole chunks and under decays that an ``exp(-cumsum g)``
could not hold, and its gradient; partial rope; the published norm's ``1 + w``
form; the fused core's blocks by shape; refusals; the scopes, the spans and the
labelled counter; the configuration file and its counts; the readers of the new
per-layer metrics; and that the architectures that were there trace the
programs they traced.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_ladder
from gentun_tpu import deepseek_v2_genome
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry import spans
from gentun_tpu.telemetry.registry import get_registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
FAMILY = os.path.join(BENCH, "families", "qwen3_next")
CELL = "qwen3_next_80b_a3b_ep16.popeval"


def _load(name):
    spec = importlib.util.spec_from_file_location(f"q3n_family_{os.path.basename(name)}", os.path.join(FAMILY, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


R = _load("reference")
flops = _load("flops")
scope_rules = _load("scope_rules")

PERIOD = ["linear_attention", "linear_attention", "linear_attention", "full_attention"]
MODEL = dict(hidden_size=40, head_dim=16, num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24,
             shared_expert_intermediate_size=24, num_experts=8, num_experts_per_tok=3, held_experts=[2, 4],
             num_hidden_layers=4, layer_types=PERIOD, vocab_size=64, rms_norm_eps=1e-6, rope_theta=1e7,
             partial_rotary_factor=0.25, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
             linear_value_head_dim=12, linear_conv_kernel_dim=4, train_steps=3)
PAIR = {**MODEL, "num_hidden_layers": 2, "layer_types": PERIOD[2:]}  # one layer of each kind: what the step tests train
GENES = dict(log10_lr=-2.5, warmup_frac=0.5, weight_decay=0.1, beta2=0.95, aux_alpha=0.05)
HIGHEST = jax.default_matmul_precision("highest")
STD = 0.15  # narrow layers: wider weights, or the operators vanish beside the residual


def model_kwargs(m=MODEL, **over):
    """``Lfm2MoeModel``'s keyword arguments that make it the reference's model ``m``: the published keys."""
    kw = {k: m[k] for k in ("hidden_size", "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                            "num_attention_heads", "num_key_value_heads", "vocab_size", "rope_theta",
                            "partial_rotary_factor", "linear_num_key_heads", "linear_num_value_heads",
                            "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim", "train_steps")}
    kw.update(layer_types=tuple(m["layer_types"]), num_dense_layers=0, held_experts=tuple(m["held_experts"]),
              norm_eps=m["rms_norm_eps"], qk_norm=True, attn_output_gate=True, n_shared_experts=1,
              shared_expert_gate=True, scoring_func="softmax", norm_topk_prob=True, balance_rule="aux_loss",
              tie_word_embeddings=False, batch_sequences=2, eval_sequences=2, attn_block=7, delta_chunk=8,
              compute_dtype="float32")
    kw.update(over)
    return kw


@pytest.fixture(scope="module")
def tokens():
    tok = np.random.default_rng(0).integers(0, 64, size=(10, 29)).astype(np.int32)  # 28 positions: three and a half chunks
    return tok[:, :-1], tok[:, 1:]


def config_of(tokens, m=MODEL, **over) -> M.Lfm2MoeConfig:
    return M.Lfm2MoeModel.compiled_programs(tokens[0], **model_kwargs(m, **over)).config


NO_BIAS = jnp.zeros((8, 8), jnp.float32)  # the state's bias: zeros, never read under the ``aux_loss`` rule

LAYER_CASES = {"a_delta_layer": {**MODEL, "num_hidden_layers": 1, "layer_types": ["linear_attention"], "held_experts": [1, 5]},
               "a_gated_attention_layer": {**MODEL, "num_hidden_layers": 1, "layer_types": ["full_attention"],
                                           "held_experts": [1, 5]},
               "one_period": MODEL}


@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_logits_loss_with_the_balance_term_and_gradients_match_the_reference(case, tokens):
    m = LAYER_CASES[case]
    cfg = config_of(tokens, m)
    assert cfg.typed_attention == ("linear_attention" in m["layer_types"]) and cfg.rotary_dim == 4
    w = R.seeded_weights(m, 7, STD)
    shapes = M.param_shapes(cfg)
    assert [a.shape for a in jax.tree_util.tree_leaves(w)] == jax.tree_util.tree_leaves(shapes, is_leaf=M._is_shape)
    assert jax.tree_util.tree_structure(w) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, shapes, is_leaf=M._is_shape))
    x, y = tokens[0][:2], tokens[1][:2]
    alpha = 0.05

    def system_loss(params):
        logits, load, stats = M.forward(cfg, params, NO_BIAS, x, remat=True)
        return M.token_loss(logits, y).mean() + alpha * stats.balance, (logits, load, stats)

    def reference_loss(params):
        out = [R.forward(m, params, xs) for xs in x]
        nll = jnp.mean(jnp.stack([R.token_loss(o[0], ys) for o, ys in zip(out, y)]))
        balance = sum(o[2] for o in out) / len(out)
        return nll + alpha * balance, (jnp.stack([o[0] for o in out]), sum(o[1] for o in out), balance)

    with HIGHEST:
        (loss, (logits, load, stats)), grads = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(w)
        (ref_loss, (ref_logits, ref_load, ref_balance)), ref_grads = jax.jit(
            jax.value_and_grad(reference_loss, has_aux=True))(w)
    np.testing.assert_allclose(logits, ref_logits, atol=3e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-6)
    np.testing.assert_allclose(stats.balance, ref_balance, rtol=2e-6)
    assert float(ref_balance) > 0.9 * m["num_hidden_layers"]  # ~1 a routed layer, and every layer is routed
    np.testing.assert_array_equal(load, ref_load)
    assert int(stats.dropped) == 0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(g, r, atol=3e-5 * max(scale, 1.0), rtol=1e-4, err_msg=jax.tree_util.keystr(path))
        assert scale > 0 or "embed" in str(path), f"{jax.tree_util.keystr(path)}: the reference's gradient is all zero"


def _program_steps(programs, weights, x, y, rows, steps, genes=GENES):
    state = programs.init(jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    state = {**state, "params": jax.tree_util.tree_map(jnp.asarray, weights)}
    losses, loads = [], []
    for s in range(steps):
        state, loss, held = programs.train_step(state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(rows),
                                                jnp.asarray(M.gene_vector(genes)), np.int32(s))
        losses.append(float(loss))
        loads.append(np.asarray(held))
    return state, losses, loads


ROWS = np.array([[0, 1], [2, 3], [4, 5]], np.int32)


def test_two_train_steps_match_the_reference(tokens):
    x, y = tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **model_kwargs(PAIR))
    assert programs.config.gene_names == tuple(deepseek_v2_genome().names)
    w = R.seeded_weights(PAIR, 5, STD)
    with HIGHEST:
        state, losses, loads = _program_steps(programs, w, x, y, ROWS, 2)
        ref = R.train(PAIR, w, [(x[r], y[r]) for r in ROWS[:2]], GENES)
    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-6)  # the balance term included
    np.testing.assert_allclose(float(state["aux_loss"]), sum(ref["balances"]), rtol=2e-6)
    for got, want in zip(loads, ref["loads"]):
        np.testing.assert_array_equal(got, want[:, 2:4])
    for (path, a), b, start in zip(jax.tree_util.tree_flatten_with_path(state["params"])[0],
                                   jax.tree_util.tree_leaves(ref["weights"]), jax.tree_util.tree_leaves(w)):
        change, ref_change = np.asarray(a) - start, np.asarray(b) - start
        assert np.abs(ref_change).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(change, ref_change, atol=1e-4, err_msg=jax.tree_util.keystr(path))
    with HIGHEST:
        got = programs.eval(state["params"], state["bias"], jnp.asarray(x), jnp.asarray(y), jnp.asarray([8, 9]))
        want = R.eval_token_loss(PAIR, ref["weights"], x[8:10], y[8:10])
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_no_weight_decay_reaches_a_log_dt_bias_or_a_norm_and_they_start_from_their_own_values(tokens):
    """Under a recipe that is all weight decay (no gradient step to speak of: a learning rate times a decay of 1),
    the leaves that are no matrix keep what the gradient alone gives them; and a run starts them at ln u, 1 and 1."""
    x, y = tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **model_kwargs(PAIR))
    start = programs.init(jax.random.PRNGKey(3), jnp.asarray([1, 2], jnp.uint32))["params"]
    delta = start["layers"][0]["delta"]
    assert float(jnp.abs(delta["dt_bias"] - 1).max()) == 0 and float(jnp.abs(delta["norm"] - 1).max()) == 0
    rates = np.exp(np.asarray(delta["A_log"]))
    assert rates.min() > 0 and rates.max() < M.DECAY_RATE_MAX and rates.std() > 0
    assert float(jnp.std(delta["kernel"])) == pytest.approx(M.INIT_STD, rel=0.3)
    w = R.seeded_weights(PAIR, 5, STD)
    with HIGHEST:
        plain, _, _ = _program_steps(programs, w, x, y, ROWS, 1, {**GENES, "weight_decay": 0.0})
        decayed, _, _ = _program_steps(programs, w, x, y, ROWS, 1, {**GENES, "weight_decay": 1.0})
    a, b = plain["params"]["layers"][0], decayed["params"]["layers"][0]
    for name in ("A_log", "dt_bias", "norm"):
        np.testing.assert_array_equal(a["delta"][name], b["delta"][name])
    np.testing.assert_array_equal(a["op_norm"], b["op_norm"])
    for name in ("kernel", "qkvz", "ba", "out"):
        assert float(jnp.abs(a["delta"][name] - b["delta"][name]).max()) > 0, name
    assert float(jnp.abs(a["moe"]["shared_gate"] - b["moe"]["shared_gate"]).max()) > 0


def test_the_published_norm_with_one_plus_w_from_zero_is_the_familys_w_from_one_over_two_steps(tokens):
    """``x_hat * (1 + w)`` with ``w`` from ``v - 1`` against ``x_hat * w`` from ``v``: the same losses and the same
    updates of every leaf over two AdamW steps with weight decay on (none reaches a norm weight in either form)."""
    x, y = tokens
    w = R.seeded_weights(PAIR, 9, STD)
    centred_names = ("op_norm", "ffn_norm", "final_norm", "q_norm", "k_norm")  # the stream's and q/k's; not the gated one
    shifted = lambda tree, by: jax.tree_util.tree_map_with_path(
        lambda path, a: np.asarray(a) + by if any(n in str(path[-1]) for n in centred_names) else np.asarray(a), tree)
    batches = [(x[r], y[r]) for r in ROWS[:2]]
    with HIGHEST:
        plain = R.train(PAIR, w, batches, GENES)
        centred = R.train({**PAIR, "zero_centred_norms": True}, shifted(w, -1.0), batches, GENES)
    np.testing.assert_allclose(plain["losses"], centred["losses"], rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(plain["weights"])[0],
                            jax.tree_util.tree_leaves(shifted(centred["weights"], 1.0))):  # w against 1 + w
        np.testing.assert_allclose(np.asarray(a), b, atol=1e-5, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("kind", PERIOD[2:])
def test_the_shares_with_what_every_rank_computes_counted_once_add_up_to_the_uncut_layer(kind, tokens):
    """8 experts in 4 shares of 2, 5 a token: each share's program computes the mixer, the residual, the gated
    shared expert and its own routed experts' part, the weights normalised over all the chosen five; the routed
    parts, with what every share computes alike counted once, are the uncut reference's layer output."""
    m = {**MODEL, "num_hidden_layers": 1, "layer_types": [kind], "num_experts": 8, "num_experts_per_tok": 5}
    x = tokens[0][:2]
    uncut = {**m, "held_experts": [0, 8]}
    w_all = R.seeded_weights(uncut, 11, STD)
    layer_w = w_all["layers"][0]
    embedded = w_all["embed"][x]
    share_of = lambda first, last: dict(layer_w, moe={k: (v[first:last] if k in ("w1", "w3", "w2") else v)
                                                      for k, v in layer_w["moe"].items()})
    identity = lambda a: a
    with HIGHEST:
        whole = jnp.stack([R.layer(uncut, 0, identity, layer_w, jnp.asarray(e))[0] for e in embedded])
        # mixer, residual and the gated shared expert, no routed expert: what every share computes alike
        alike = jnp.stack([R.layer({**uncut, "held_experts": [0, 0]}, 0, identity, share_of(0, 0), jnp.asarray(e))[0]
                           for e in embedded])
        without_shared = jnp.stack([R.layer({**uncut, "held_experts": [0, 0], "shared_expert": False}, 0, identity,
                                            share_of(0, 0), jnp.asarray(e))[0] for e in embedded])
        total = alike
        for first in range(0, 8, 2):
            cfg = config_of(tokens, {**m, "held_experts": [first, first + 2]})
            out, _ = jax.jit(lambda p, e, cfg=cfg: M._layer(cfg, 0, jnp.float32, p, None, e))(
                share_of(first, first + 2), jnp.asarray(embedded))
            part = out - alike
            assert float(jnp.abs(part).max()) > 0
            total = total + part
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert float(jnp.abs(whole - alike).max()) > 1e-3, "the routed experts are part of the layer"
    assert float(jnp.abs(alike - without_shared).max()) > 1e-3, "and so is the shared expert, once"


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


# -- partial rope, the blocks by shape ------------------------------------------------------------------------------


def test_partial_rope_turns_the_leading_quarter_and_leaves_the_other_columns_alone():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 24, 2, 3, 256)), jnp.float32)
    got = M._rope_whole_heads(x, 1e7, None, 64)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    np.testing.assert_allclose(got[..., :64], M._rope(x[..., :64], 1e7), atol=1e-6)  # rotate-half inside the 64: (c, c + 32)
    want = jnp.stack([R.partial_rope(xs.reshape(24, 6, 256), {"head_dim": 256, "partial_rotary_factor": 0.25,
                                                              "rope_theta": 1e7}).reshape(24, 2, 3, 256) for xs in x])
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert float(jnp.abs(got[:, 1:, ..., :64] - x[:, 1:, ..., :64]).max()) > 0.1
    np.testing.assert_array_equal(M._rope_whole_heads(x, 1e7, None, None), M._rope_whole_heads(x, 1e7))


@pytest.mark.parametrize("qk,v,q_dkv", [(64, 64, 1024), (128, 128, 1024), (192, 128, 1024), (256, 256, 512)])
def test_the_fused_cores_backward_query_block_follows_the_columns_it_holds(qk, v, q_dkv):
    """LFM2's, Mellum2's and latent attention's heads keep the blocks chip runs set; a head size of 256 for q, k
    and v halves the backward kernel's query block (1,024 asked for 16.57 MB of the 16 the kernel may take)."""
    blocks = M._kernel_blocks(16384, M._core_columns(qk, v))
    assert blocks["block_q_dkv"] == q_dkv
    assert {k: s for k, s in blocks.items() if k != "block_q_dkv"} == \
        {k: s for k, s in M._ATTN_KERNEL_BLOCKS.items() if k != "block_q_dkv"}
    assert M._kernel_blocks(16384) == M._ATTN_KERNEL_BLOCKS and M._kernel_blocks(100, 512) is None


def test_flops_counts_the_block_pairs_the_kernel_would_visit():
    visits = flops.block_visits(16384)
    assert visits == {"pairs": 136, "elements": 136 * 1024 * 1024, "pairs_bwd": 272, "elements_bwd": 272 * 512 * 1024}
    assert flops.KERNEL_BLOCKS["backward"] == (M._kernel_blocks(16384, 512)["block_q_dkv"], M._kernel_blocks(16384, 512)["block_kv_dkv"])


# -- refusals, scopes, spans ----------------------------------------------------------------------------------------


@pytest.mark.parametrize("bad,why", [
    (dict(linear_num_value_heads=3), "linear_attention layer needs"),
    (dict(linear_key_head_dim=0), "linear_attention layer needs"),
    (dict(delta_chunk=0), "linear_attention layer needs"),
    (dict(partial_rotary_factor=0.0), "partial_rotary_factor"),
    (dict(partial_rotary_factor=0.2), "partial_rotary_factor"),
    (dict(n_shared_experts=0), "shared_expert_gate"),
    (dict(layer_types=("linear_attention", "mamba")), "layer_types"),
])
def test_a_configuration_that_cannot_run_is_refused_before_anything_compiles(tokens, bad, why):
    with pytest.raises(ValueError, match=why):
        M.Lfm2MoeModel.compiled_programs(tokens[0], **model_kwargs(**bad))


def test_the_kinds_of_layer_say_which_are_attention_over_keys():
    assert set(M.ATTENTION_KINDS) == {"full_attention", "sliding_attention", "latent_attention"}
    assert set(M.LAYER_KINDS) == set(M.ATTENTION_KINDS) | {"conv", "linear_attention"}
    lfm2 = M.Lfm2MoeConfig()
    assert not lfm2.typed_attention and lfm2.rotary_dim == lfm2.head_dim == 64
    assert M.Lfm2MoeConfig(layer_types=("linear_attention", "full_attention"), layer_ids=(0, 1)).typed_attention


def _scopes(fn, *args):
    import re

    found = set()

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            stack = "/".join(filter(None, (outer, re.sub(r"[A-Za-z_]+\(|\)", "", str(eqn.source_info.name_stack)))))
            if stack:
                found.add(stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, stack)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return found


def test_each_layer_type_has_its_own_scope_with_its_parts_inside(tokens):
    cfg = config_of(tokens)
    w = jax.tree_util.tree_map(jnp.asarray, R.seeded_weights(MODEL, 1, STD))
    scopes = _scopes(lambda p: M.forward(cfg, p, NO_BIAS, tokens[0][:2])[0], w)
    parts = {"linear_attention": ("proj", "conv", "gates", "core", "norm_gate"),
             "full_attention": ("proj", "rope", "core", "gate")}
    for layer, kind in enumerate(PERIOD):
        for part in parts[kind]:
            assert any(s.startswith(f"layer{layer}/{kind}/{part}") for s in scopes), (layer, kind, part)
        other = "full_attention" if kind == "linear_attention" else "linear_attention"
        assert not any(s.startswith(f"layer{layer}/{other}") or s.startswith(f"layer{layer}/attention") for s in scopes)
        assert any(s.startswith(f"layer{layer}/moe/shared") for s in scopes)
    classify = scope_rules.classify
    assert classify("jit(lm_train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/layer1/linear_attention/"
                    "core/triangular_solve") == ("delta_core", "core")
    assert classify("jit(lm_train_step)/jvp(layer0)/linear_attention/core/closed_call/while/body/dot_general") == \
        ("delta_core", "core")
    assert classify("layer0/linear_attention/conv/mul") == ("delta_conv", "conv")
    assert classify("layer0/linear_attention/proj/dot_general") == ("delta_proj", "proj")
    assert classify("layer0/linear_attention/gates/logistic") == ("delta_proj", "gates")
    assert classify("layer2/linear_attention/norm_gate/mul") == ("delta_proj", "norm_gate")
    assert classify("jit(lm_train_step)/transpose(jvp(layer3))/full_attention/core/splash") == ("full_core", "core")
    assert classify("layer3/full_attention/gate/logistic") == ("attention_proj", "gate")
    assert classify("layer3/full_attention/rope/mul") == ("attention_proj", "rope")
    assert classify("layer0/moe/shared/dot_general") == ("shared_expert", "shared")
    assert classify("layer0/cond/branch_1_fun/moe/experts/gmm") == ("expert_mm", "experts")
    assert classify("layer0/aux_loss/mul") == ("moe_route", "aux_loss")
    assert classify("optimizer/add") == ("optimizer", "optimizer") and classify("") == ("unattributed", "")
    assert {classify(s)[0] for s in scopes} <= set(scope_rules.CLASSES)


class _Sink:
    def __init__(self):
        self.records = []

    def record(self, rec):
        self.records.append(rec)


@pytest.fixture(scope="module")
def two_traced_individuals(tokens):
    """Two individuals of two delta layers and a full one scored with telemetry on: (programs, the ``train``
    spans' attributes, the labelled counter's value, the fitnesses)."""
    x, y = tokens
    kw = model_kwargs({**MODEL, "num_hidden_layers": 3, "layer_types": PERIOD[1:]}, cache_dir=False)
    programs = M.Lfm2MoeModel.compiled_programs(x, **kw)

    sink = _Sink()
    get_registry().reset()
    spans.set_run_sink(sink)
    spans.enable()
    try:
        fitness = M.Lfm2MoeModel.cross_validate_population(x, y, [deepseek_v2_genome().default()] * 2, **kw)
    finally:
        spans.disable()
        spans.set_run_sink(None)
    trained = [r["attrs"] for r in sink.records if r["type"] == "span" and (r.get("attrs") or {}).get("steps") == 3]
    by_kernel = get_registry().counter("linear_core_kernel_layer_steps_total").value
    return programs, trained, get_registry().counter("linear_core_layer_steps_total", program="chunked").value, fitness, by_kernel


def test_spans_and_the_labelled_counter_say_which_program_the_delta_core_ran_as(two_traced_individuals):
    programs, trained, counted, fitness, _ = two_traced_individuals
    assert programs.linear_core_layers == (("chunked", 2),) and programs.kernel_layers_by_mask == (("causal", 0),)
    assert np.isfinite(fitness).all() and fitness[0] == fitness[1]
    assert [a["linear_core_layer_steps_chunked"] for a in trained] == [6, 6] and trained[0]["linear_core_chunk"] == 8
    assert trained[0]["attention_kernel_layer_steps_causal"] == 0
    assert counted == 12
    lfm2 = M.Lfm2MoeModel.compiled_programs(np.zeros((6, 16), np.int32), hidden_size=32, layer_types=("conv", "full_attention"),
                                            num_dense_layers=1, intermediate_size=48, moe_intermediate_size=24,
                                            num_experts=8, num_experts_per_tok=2, held_experts=(0, 2), num_attention_heads=4,
                                            num_key_value_heads=2, vocab_size=64, batch_sequences=2, eval_sequences=2,
                                            attn_block=8, compute_dtype="float32")
    assert lfm2.linear_core_layers == ()


def test_the_train_span_says_how_many_products_a_chunk_step_runs_in_sequence(two_traced_individuals):
    """``linear_core_chain_products`` beside ``linear_core_chunk``: a trace says which form of the core ran."""
    _, trained, _, _, _ = two_traced_individuals
    assert [(a["linear_core_chunk"], a["linear_core_chain_products"]) for a in trained] == [(8, 1)] * 2
    assert M.LINEAR_CORE_PROGRAMS == ("chunked",)


def test_the_train_span_says_what_a_chunks_inverse_costs_in_products(two_traced_individuals):
    """``linear_core_inverse_products``: nothing where XLA's ops solve the system by substitution; where the kernels
    run, what the function that chose each level's form counts (the engaged programs' span is read in the next test):
    under PR 43's ten at the published shape, ten where no level's live rows are whole sublanes."""
    from gentun_tpu.models import delta_kernel

    programs, trained, _, _, _ = two_traced_individuals
    assert programs.linear_core_inverse_products == 0 and [a["linear_core_inverse_products"] for a in trained] == [0, 0]
    assert delta_kernel.inverse_products(64, 2) == 5 and delta_kernel.inverse_products(24, 3) == 10


def test_the_train_span_says_whether_the_delta_core_ran_as_the_fused_kernels(two_traced_individuals, tokens, monkeypatch):
    """On the CPU XLA's ops run: ``linear_core_kernel_layer_steps`` is there and 0, and what the benchmark reads
    (``linear_core_layer_steps_chunked``, ``linear_core_chunk``) keeps its values; with the kernels chosen (and
    interpreted) the same programs report their layers x steps, two products on the chain, the same fitness."""
    from gentun_tpu.models import delta_kernel

    programs, trained, _, fitness, by_kernel = two_traced_individuals
    assert programs.linear_core_kernel_layers == 0 and by_kernel == 0
    assert [(a["linear_core_kernel_layer_steps"], a["linear_core_layer_steps_chunked"], a["linear_core_chunk"])
            for a in trained] == [(0, 6, 8)] * 2
    monkeypatch.setattr(M, "_use_delta_kernel", lambda dk, dv, chunk, heads: True)
    monkeypatch.setattr(delta_kernel, "delta_core", functools.partial(delta_kernel.delta_core, interpret=True))
    M._programs.cache_clear()
    x, y = tokens
    kw = model_kwargs({**MODEL, "num_hidden_layers": 3, "layer_types": PERIOD[1:]}, cache_dir=False)
    sink = _Sink()
    get_registry().reset()
    spans.set_run_sink(sink)
    spans.enable()
    try:
        by_kernels = M.Lfm2MoeModel.cross_validate_population(x, y, [deepseek_v2_genome().default()], **kw)
        engaged = M.Lfm2MoeModel.compiled_programs(x, **kw)
    finally:
        spans.disable()
        spans.set_run_sink(None)
        M._programs.cache_clear()
    attrs = next(r["attrs"] for r in sink.records if r["type"] == "span" and (r.get("attrs") or {}).get("steps") == 3)
    assert engaged.linear_core_kernel_layers == 2 and engaged.linear_core_layers == (("chunked", 2),)
    assert (attrs["linear_core_kernel_layer_steps"], attrs["linear_core_layer_steps_chunked"], attrs["linear_core_chunk"],
            attrs["linear_core_chain_products"]) == (6, 6, 8, M.LINEAR_CORE_KERNEL_CHAIN_PRODUCTS)
    assert attrs["linear_core_inverse_products"] == engaged.linear_core_inverse_products == delta_kernel.inverse_products(8, 2) == 2
    assert get_registry().counter("linear_core_kernel_layer_steps_total").value == 6
    assert get_registry().counter("linear_core_layer_steps_total", program="chunked").value == 6
    np.testing.assert_allclose(by_kernels[0], fitness[0], rtol=1e-5)


# -- the benchmark's family: configuration file, counts, readers ----------------------------------------------------


def _config_file():
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b_a3b_ep16.json")) as fh:
        return json.load(fh)


@pytest.fixture()
def family_modules():
    """The family's files as ``run.py`` loads them (its directory and the harness's on ``sys.path``)."""
    names = ("q3n_spans", "scope_rules", "scope_reduce", "spanlib", "trace_reduce", "stall_reduce", "flops", "family",
             "correct", "reference")
    before = {n: sys.modules.pop(n, None) for n in names}
    sys.path[:0] = [FAMILY, BENCH]
    try:
        yield _load
    finally:
        del sys.path[:2]
        for n in names:
            sys.modules.pop(n, None)
            if before[n] is not None:
                sys.modules[n] = before[n]


def test_the_configuration_file_holds_the_catalogs_numbers_and_the_cut_is_the_bytes_it_says(family_modules):
    config = _config_file()
    published = dict(hidden_size=2048, head_dim=256, num_attention_heads=16, num_key_value_heads=2,
                     partial_rotary_factor=0.25, rope_theta=10000000, linear_num_key_heads=16, linear_num_value_heads=32,
                     linear_key_head_dim=128, linear_value_head_dim=128, linear_conv_kernel_dim=4, num_experts=512,
                     num_experts_per_tok=10, moe_intermediate_size=512, shared_expert_intermediate_size=512,
                     intermediate_size=5120, full_attention_interval=4, max_position_embeddings=262144,
                     rms_norm_eps=1e-6, decoder_sparse_step=1, mlp_only_layers=[], norm_topk_prob=True,
                     tie_word_embeddings=False, model_type="qwen3_next", rope_scaling=None)
    for key, value in published.items():
        assert config[key] == value, key
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "train_steps", "n_sequences"}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] == 151936
    assert (config["num_hidden_layers"], config["num_experts_held"], config["layers_kept"]) == (4, 32, [0, 1, 2, 3])
    family = family_modules("family")
    params, m = family.model_params(config, 5, rehearsal=False), family.model_block(config)
    assert m["layer_types"] == PERIOD and family.layer_types({**config, "layers_kept": list(range(8))}) == PERIOD * 2
    params.pop("seed")
    cfg = M._normalize_config(np.zeros((config["n_sequences"], config["data"]["seq_len"]), np.int32), params)[0]
    need = M.training_bytes(cfg)
    assert need["params"] == 625_667_136 and need["state"] == 10_010_674_176
    assert need["total"] < 15.75 * 2**30, "one individual fits the chip by arithmetic"
    assert (cfg.tokens_per_step, cfg.batch_sequences, cfg.seq_len, cfg.delta_chunk) == (16384, 1, 16384, 64)
    assert M._row_buffer_heights(cfg, 16384) == (12800, 28160, 163840)  # 1.25 and 2.75 shares of 10,240, and 16
    assert cfg.typed_attention and cfg.rotary_dim == 64 and cfg.attn_output_gate and cfg.shared_expert_gate
    shapes = M.param_shapes(cfg)
    assert shapes["layers"][0]["delta"] == {"qkvz": (2048, 12288), "ba": (2048, 64), "kernel": (8192, 4), "A_log": (32,),
                                            "dt_bias": (32,), "norm": (128,), "out": (4096, 2048)}
    assert shapes["layers"][3]["attn"] == {"q": (2048, 8192), "k": (2048, 512), "v": (2048, 512), "o": (4096, 2048),
                                           "q_norm": (256,), "k_norm": (256,)}
    assert shapes["layers"][3]["moe"]["w1"] == (32, 2048, 512) and shapes["layers"][3]["moe"]["shared_gate"] == (2048,)
    assert shapes["head"] == (18992, 2048) == shapes["embed"]
    # the executed FLOPs of a step, by flops.py: the count PERF.md's prediction rests on
    total = flops.train_flops(m, 16384, 10240 * 4, 16384)
    assert 33e12 < total < 38e12, total  # 35.5 TFLOP a step
    assert 70e9 < flops.delta_core_flops(m, 1, 16384, 64, 1, 0) < 85e9  # 77 GFLOP a layer forward


def test_require_fit_accepts_the_published_cut_on_a_chip_of_sixteen_gigabytes(monkeypatch, family_modules):
    config = _config_file()
    params = family_modules("family").model_params(config, 5, rehearsal=False)
    params.pop("seed")
    cfg = M._normalize_config(np.zeros((config["n_sequences"], config["data"]["seq_len"]), np.int32), params)[0]

    class Device:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return {"bytes_limit": self.limit}

    monkeypatch.setattr(jax, "local_devices", lambda: [Device(int(15.75 * 2**30))])
    M._require_fit(cfg)
    monkeypatch.setattr(jax, "local_devices", lambda: [Device(8 * 2**30)])
    with pytest.raises(ValueError, match="one individual needs"):
        M._require_fit(cfg)


def test_the_cell_runs_the_accepted_mix_as_it_is(family_modules):
    config = _config_file()
    with open(os.path.join(BENCH, "traffic", "lmpopeval_fresh.json")) as f:
        mix = json.load(f)
    family = family_modules("family")
    small = {**config, "n_sequences": 4, "data": {**config["data"], "seq_len": 16}}
    a, b, again = (family.make_inputs(small, mix, seed) for seed in (3, 2147484001, 3))
    pool = a["pool"]
    assert pool == b["pool"] and len(pool) == config["population"] == 4
    assert pool[0] == {"log10_lr": -3.5, "warmup_frac": 0.25, "weight_decay": 0.1, "beta2": 0.95, "aux_alpha": 0.001}
    assert all(r["log10_lr"] <= mix["pool_log10_lr_max"] for r in pool) and "pool_log10_lr_max" not in config
    assert np.array_equal(a["x"], again["x"]) and a["x"].shape == (4, 16) and np.array_equal(a["x"][:, 1:], a["y"][:, :-1])
    assert int(a["x"].max()) < config["vocab_size"]


def _span(kind, t, attrs):
    return {"type": "span", "kind": kind, "t_wall": t, "dur_s": 0.001, "attrs": attrs}


def test_the_layer_step_readers_read_the_windows_train_spans_and_the_parent_reads_nothing(family_modules):
    chunked = family_modules(os.path.join("..", "..", "layer_metrics", "q3n_delta_chunked_layer_steps"))
    kernel = family_modules(os.path.join("..", "..", "layer_metrics", "q3n_full_kernel_layer_steps"))
    train = lambda t, **attrs: _span("train", t, {"individual": 0, "steps": 8, **attrs})
    window = {"window": (10.0, 20.0)}
    records = [train(5.0, linear_core_layer_steps_chunked=0, attention_kernel_layer_steps_causal=0),  # set-up
               train(11.0, linear_core_layer_steps_chunked=24, attention_kernel_layer_steps_causal=8),
               train(12.0, linear_core_layer_steps_chunked=24, attention_kernel_layer_steps_causal=8),
               _span("train", 13.0, {"fold": 0, "linear_core_layer_steps_chunked": 99})]  # no span of this family
    assert chunked.read({**window, "records": records}) == 24 and kernel.read({**window, "records": records}) == 8
    assert chunked.read({**window, "records": [train(11.0, attention_kernel_layer_steps=64)]}) is None  # the parent
    assert kernel.read({**window, "records": records[:1]}) is None


def test_the_row_buffer_reader_divides_the_rows_the_heights_ran_by_the_rows_routed(family_modules):
    reader = family_modules(os.path.join("..", "..", "layer_metrics", "q3n_row_buffer_rows_per_routed_row"))
    routed_ladder.assert_the_reader_divides_the_rows_run_by_the_rows_routed(reader)


def test_every_q3n_metric_of_the_manifest_has_a_reader_and_reads_nothing_from_an_empty_run(family_modules):
    """A program that lacks the spans (the parent's, on the new cell's readers) makes no reader raise."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    names = [m["name"] for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert len(names) == 34 and all(n.startswith("q3n_") for n in names), names
    assert not [m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", []) and not m["name"].startswith("q3n_")]
    empty = {"config": _config_file(), "cell": {"name": CELL}, "chips": 1, "units": [], "records": [],
             "window": (0.0, 1.0), "elapsed": 1.0, "monitor": None, "trace": None, "memory_peak_bytes": 0, "peak": None}
    for name in names:
        assert family_modules(os.path.join("..", "..", "layer_metrics", name)).read(dict(empty)) is None, name
