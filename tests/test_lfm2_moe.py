"""The LFM2-MoE family (``models/lfm2_moe.py``) against its plain reference
(``benchmark/families/lfm2_moe/reference.py``), at small sizes on the CPU.

System and reference are compared in float32 on seeded weights: per layer kind
and whole on logits, loss and gradients; over two train steps on loss,
parameter change and the router bias; the share test ties the expert layer's
cut (``held_experts``) to the uncut layer; fitness is a pure function of
genome, configuration and seed in any position of any call, telemetry on or
off; and the species runs through ``Population`` and ``GeneticAlgorithm``.  The
fused attention kernel (the TPU's core) runs here in Pallas' interpret mode
against the blockwise XLA core, the one every other test of this file takes.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_ladder
from gentun_tpu import GeneticAlgorithm, Lfm2MoeIndividual, Population, lfm2_moe_genome
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry import spans
from gentun_tpu.telemetry.registry import get_registry

FAMILY = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "families", "lfm2_moe")


def _load(name):
    spec = importlib.util.spec_from_file_location(f"lfm2_family_{name}", os.path.join(FAMILY, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


R = _load("reference")
flops = _load("flops")
scope_rules = _load("scope_rules")

MODEL = dict(hidden_size=32, layer_types=["conv", "full_attention", "conv"], num_dense_layers=1, intermediate_size=48,
             moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2, held_experts=[2, 4],
             num_attention_heads=4, num_key_value_heads=2, vocab_size=64, conv_L_cache=3, norm_eps=1e-5,
             rope_parameters={"rope_theta": 1e6}, train_steps=3)
GENES = dict(log10_lr=-2.5, warmup_frac=0.5, weight_decay=0.1, beta2=0.95, bias_step=0.01)
HIGHEST = jax.default_matmul_precision("highest")


def model_kwargs(m=MODEL, **over):
    kw = {k: v for k, v in m.items() if k != "rope_parameters"}
    kw.update(rope_theta=m["rope_parameters"]["rope_theta"], batch_sequences=2, eval_sequences=2, attn_block=8,
              compute_dtype="float32")
    kw.update(over)
    return kw


@pytest.fixture(scope="module")
def tokens():
    tok = np.random.default_rng(0).integers(0, 64, size=(10, 17)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


@pytest.fixture(scope="module")
def bias():
    return (0.1 * np.random.default_rng(1).normal(size=(2, 8))).astype(np.float32)


def config_of(tokens, m=MODEL, **over) -> M.Lfm2MoeConfig:
    return M.Lfm2MoeModel.compiled_programs(tokens[0], **model_kwargs(m, **over)).config


def one_layer(kind: str, ffn: str):
    """A one-layer model of the given operator and feed-forward (a dense layer
    cannot stand alone: the program needs a routed one, so it leads one)."""
    types = [kind] if ffn == "moe" else [kind, "conv"]
    return {**MODEL, "layer_types": types, "num_dense_layers": 0 if ffn == "moe" else 1, "held_experts": [1, 5]}


LAYER_CASES = {"conv_moe": one_layer("conv", "moe"), "attention_moe": one_layer("full_attention", "moe"),
               "conv_dense": one_layer("conv", "dense"), "attention_dense": one_layer("full_attention", "dense"),
               "whole_cut": {**MODEL, "held_experts": [1, 5]}}


@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_logits_loss_and_gradients_match_the_reference(case, tokens):
    m = LAYER_CASES[case]
    cfg = config_of(tokens, m)
    w = R.seeded_weights(m, 7)
    n_routed = len(m["layer_types"]) - m["num_dense_layers"]
    b = jnp.asarray(0.1 * np.random.default_rng(2).normal(size=(n_routed, 8)), jnp.float32)
    x, y = tokens[0][:2], tokens[1][:2]

    def system_loss(params):
        logits, load, use = M.forward(cfg, params, b, x, remat=True)
        return M.token_loss(logits, y).mean(), (logits, load, use)

    def reference_loss(params):
        out = [R.forward(m, params, b, xs) for xs in x]
        logits = jnp.stack([o[0] for o in out])
        return jnp.mean(jnp.stack([R.token_loss(l, ys) for l, ys in zip(logits, y)])), (logits, sum(o[1] for o in out))

    with HIGHEST:
        (loss, (logits, load, use)), grads = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(w)
        (ref_loss, (ref_logits, ref_load)), ref_grads = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(w)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    np.testing.assert_array_equal(load, ref_load)
    assert int(use.dropped) == 0 and int(use.wide) == 0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(g, r, atol=2e-6, rtol=1e-4, err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(r).max()) > 0, f"{jax.tree_util.keystr(path)}: the reference's gradient is all zero"


def _program_steps(programs, weights, bias, x, y, rows, steps):
    state = programs.init(jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    state = {**state, "params": jax.tree_util.tree_map(jnp.asarray, weights), "bias": jnp.asarray(bias)}
    losses, loads = [], []
    for s in range(steps):
        state, loss, held = programs.train_step(state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(rows),
                                                jnp.asarray(M.gene_vector(GENES)), np.int32(s))
        losses.append(float(loss))
        loads.append(np.asarray(held))
    return state, losses, loads


def test_two_train_steps_match_the_reference(tokens, bias):
    x, y = tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **model_kwargs())
    w = R.seeded_weights(MODEL, 5)
    rows = np.array([[0, 1], [2, 3], [4, 5]], np.int32)
    with HIGHEST:
        state, losses, loads = _program_steps(programs, w, bias, x, y, rows, 2)
        ref = R.train(MODEL, w, [(x[r], y[r]) for r in rows[:2]], GENES, bias=bias)
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-6)
    for got, want in zip(loads, ref["loads"]):
        np.testing.assert_array_equal(got, want[:, 2:4])
    np.testing.assert_array_equal(np.asarray(state["rows"]), sum(l[:, 2:4] for l in ref["loads"]))
    np.testing.assert_allclose(state["bias"], ref["bias"], atol=1e-7)
    assert np.abs(np.asarray(state["bias"]) - bias).max() == pytest.approx(0.02, rel=1e-5)  # two steps of 0.01
    for (path, a), b, start in zip(jax.tree_util.tree_flatten_with_path(state["params"])[0],
                                   jax.tree_util.tree_leaves(ref["weights"]), jax.tree_util.tree_leaves(w)):
        change, ref_change = np.asarray(a) - start, np.asarray(b) - start
        assert np.abs(ref_change).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(change, ref_change, atol=3e-5, err_msg=jax.tree_util.keystr(path))
    with HIGHEST:
        got = programs.eval(state["params"], state["bias"], jnp.asarray(x), jnp.asarray(y), jnp.asarray([8, 9]))
        want = R.eval_token_loss(MODEL, ref["weights"], ref["bias"], x[8:10], y[8:10])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(tokens, bias):
    """8 experts in 4 shares of 2: each share's program computes operator,
    residual and its own experts' part; the parts, with what every share
    computes alike counted once, are the uncut reference's layer output."""
    m = one_layer("conv", "moe")
    x = tokens[0][:2]
    uncut = {**m, "held_experts": [0, 8]}
    w_all = R.seeded_weights(uncut, 11)
    b = jnp.asarray(bias[:1])
    embedded = w_all["embed"][x]

    def layer_out(cfg, weights):
        out, _ = M._layer(cfg, 0, jnp.float32, weights["layers"][0], b[0], jnp.asarray(embedded))
        return out

    with HIGHEST:
        whole = jnp.stack([R.layer(uncut, 0, lambda a: a, w_all["layers"][0], b[0], jnp.asarray(e))[0]
                           for e in embedded])
        no_experts = dict(w_all["layers"][0], moe={k: (v if k == "router" else v[:0])
                                                   for k, v in w_all["layers"][0]["moe"].items()})
        alike = jnp.stack([R.layer({**uncut, "held_experts": [0, 0]}, 0, lambda a: a, no_experts, b[0],
                                   jnp.asarray(e))[0] for e in embedded])  # operator and residual, no expert
        total = alike
        for first in range(0, 8, 2):
            cfg = config_of(tokens, {**m, "held_experts": [first, first + 2]})
            share = {"layers": [dict(w_all["layers"][0], moe={
                k: (v if k == "router" else v[first:first + 2]) for k, v in w_all["layers"][0]["moe"].items()})]}
            part = layer_out(cfg, share) - alike
            assert float(jnp.abs(part).max()) > 0
            total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_no_assignment_is_dropped_when_every_token_goes_to_one_held_expert(tokens):
    m = one_layer("conv", "moe")
    cfg = config_of(tokens, m)
    w = R.seeded_weights(m, 3)["layers"][0]["moe"]
    forced = jnp.zeros((1, 8), jnp.float32).at[0, 3].set(10.0)  # expert 3 (held) wins every token's first choice
    x = jnp.asarray(np.random.default_rng(4).normal(size=(32, 32)), jnp.float32)
    out, load, use = M._moe_ffn(w, forced[0], x, cfg, jnp.float32)
    assert int(load[3]) == 32 and int(use.dropped) == 0 and int(use.wide) == 0  # one height at this size
    with HIGHEST:
        ref, _ = R.routed_ffn(w, forced[0], x, m, lambda a: a)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    # more rows than the narrow height holds: the layer takes the worst-case height, and says so
    out, load, use = jax.jit(lambda: M._moe_ffn(w, forced[0], x, cfg, jnp.float32, row_buffer=16))()
    assert int(load[1:5].sum()) > 16 and int(use.dropped) == 0 and int(use.wide) == 1
    np.testing.assert_allclose(out, ref, atol=1e-5)


#: A small shape at which the configuration itself gives the ladder's three heights:
#: 2 x 512 tokens, top-2, 2 of 8 experts held -- 1,024 and 1,536 rows (two and three
#: ``gmm`` tiles hold 1.25 and 2.75 times the mean share of 512) under the worst
#: case of 2,048.
LADDER = {**MODEL, "layer_types": ["conv"], "num_dense_layers": 0}
LADDER_HEIGHTS = (1024, 1536, 2048)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("count,rung", routed_ladder.counts_at_the_rungs(LADDER_HEIGHTS))
def test_the_narrow_and_the_wide_row_buffer_give_the_same_layer(long_tokens, count, rung, dtype, tol):
    """Rows that fill a rung of the ladder to its last row, and one row more
    (the next rung engages): the branch the ``switch`` takes and the worst-case
    height alone are the same function of the same rows, value and gradients."""
    cfg = config_of(long_tokens, LADDER)
    assert M._row_buffer_heights(cfg, cfg.tokens_per_step) == LADDER_HEIGHTS
    w = R.seeded_weights(LADDER, 3)["layers"][0]["moe"]
    b = jnp.asarray(0.01 * np.random.default_rng(5).normal(size=8), jnp.float32)
    routed_ladder.assert_the_ladders_layer_is_the_worst_case_heights(cfg, w, b, cfg.tokens_per_step, count, rung,
                                                                     dtype, tol)


@pytest.fixture(scope="module")
def long_tokens():
    tok = np.random.default_rng(9).integers(0, 64, size=(6, 513)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


class _Sink:
    def __init__(self):
        self.records = []

    def record(self, rec):
        self.records.append(rec)


def test_a_train_step_on_a_forced_bias_counts_the_layers_that_took_the_wide_buffer(long_tokens):
    x, y = long_tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **model_kwargs(LADDER))
    cfg = programs.config
    assert M._row_buffer_heights(cfg, cfg.tokens_per_step) == LADDER_HEIGHTS
    # the published cut: 20 and 44 tiles under the worst case (44 tiles are the 2.75 shares of PR 29)
    assert M._row_buffer_heights(M.Lfm2MoeConfig(), 16384) == (10240, 22528, 65536)
    w = R.seeded_weights(LADDER, 5)
    rows = np.array([[0, 1], [2, 3], [0, 2]], np.int32)
    forced = np.zeros((1, 8), np.float32)
    forced[0, 2:4] = 10.0  # both choices of every token go to the two held experts: 2,048 rows
    with HIGHEST:
        state, losses, loads = _program_steps(programs, w, forced, x, y, rows, 2)
        ref = R.train(LADDER, w, [(x[r], y[r]) for r in rows[:2]], GENES, bias=forced)
        calm, _, calm_loads = _program_steps(programs, w, np.zeros((1, 8), np.float32), x, y, rows, 2)
    assert all(int(l.sum()) == 2048 for l in loads) and all(int(l.sum()) <= 1536 for l in calm_loads)
    assert state["row_buffer_heights"].tolist() == [0, 0, 2] and int(state["dropped"]) == 0
    rungs = [int(np.searchsorted(LADDER_HEIGHTS, int(l.sum()))) for l in calm_loads]  # the first height that holds them
    assert calm["row_buffer_heights"].tolist() == np.bincount(rungs, minlength=3).tolist() and int(calm["dropped"]) == 0
    assert calm["row_buffer_heights"][-1] == 0 and int(calm["row_buffer_heights"].sum()) == 2  # 1 routed layer x 2 steps
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(state["params"])[0],
                            jax.tree_util.tree_leaves(ref["weights"])):
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=jax.tree_util.keystr(path))
    # telemetry on: the same counts on the individual's fetch span and on the counters
    forcing = programs._replace(init=lambda key, genome_hash: {**programs.init(key, genome_hash),
                                                                "bias": jnp.asarray(forced)})
    for scored, by_height in ((forcing, [0, 0, 3]), (programs, None)):
        get_registry().reset()
        sink = _Sink()
        spans.set_run_sink(sink)
        spans.enable()
        try:
            M._score_one(scored, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32), M.gene_vector(GENES), jnp.asarray(x),
                         jnp.asarray(y), jnp.asarray(rows), [jnp.asarray([4, 5])], [np.int32(s) for s in range(3)], 0)
        finally:
            spans.disable()
            spans.set_run_sink(None)
        (fetched,) = [r["attrs"] for r in sink.records if r["type"] == "span" and r["kind"] == "fetch"]
        heights, taken = zip(*fetched["row_buffer_heights"])
        assert heights == LADDER_HEIGHTS and sum(taken) == 3 and fetched["dropped"] == 0  # 3 steps x 1 routed layer
        assert by_height is None or list(taken) == by_height
        assert fetched["wide_buffer"] == taken[-1] == get_registry().counter("row_buffer_wide_total").value
        assert [get_registry().counter("row_buffer_height_total", rows=str(h)).value for h in heights] == list(taken)
    assert taken[-1] == 0 and fetched["wide_buffer"] == 0  # the model's own start routes under the worst case


# -- the fused attention kernel (PR 31) --------------------------------------------------------------


@pytest.fixture()
def kernel_on_the_cpu(monkeypatch):
    """The fused core chosen whatever the backend, its kernels interpreted: the
    library's own factory is given ``interpret=True``, the program has no such knob."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    monkeypatch.setattr(splash, "make_splash_mqa_single_device",
                        functools.partial(splash.make_splash_mqa_single_device, interpret=True))
    monkeypatch.setattr(M, "_use_attention_kernel", lambda length: True)
    M._programs.cache_clear()
    yield
    M._programs.cache_clear()


def _attention_case(length: int, group: int, sequences: int = 1, kv_heads: int = 1):
    """(configuration, attention weights, input) at head size 64."""
    heads = kv_heads * group
    cfg = M.Lfm2MoeConfig(hidden_size=64 * heads, num_attention_heads=heads, num_key_value_heads=kv_heads,
                          seq_len=length, attn_block=256, layer_types=("full_attention",), layer_ids=(0,),
                          num_dense_layers=0)
    rng = np.random.default_rng(length + group)
    shapes = M.param_shapes(cfg)["layers"][0]["attn"]
    p = {name: jnp.asarray(1.0 + 0.1 * rng.normal(size=shape) if "norm" in name
                           else rng.normal(size=shape) / np.sqrt(shape[0]), jnp.float32)
         for name, shape in shapes.items()}
    x = jnp.asarray(rng.normal(size=(sequences, length, cfg.hidden_size)), jnp.bfloat16)
    return cfg, p, x


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("length,group", [(512, 1), (512, 4), (1024, 1), (1024, 4)])
def test_the_fused_core_is_the_blockwise_core_to_bfloat16(length, group, kernel_on_the_cpu):
    """One ``_attention`` call by both cores, products in bfloat16: the output
    within two bfloat16 steps of its size (2^-7 of the largest entry), the
    gradients of the input, of the three projections and, on the cores alone, of
    q, k and v within 1% in norm (the cores round their probabilities once each,
    in different places)."""
    cfg, p, x = _attention_case(length, group)
    probe = jnp.asarray(np.random.default_rng(1).normal(size=x.shape), jnp.float32)

    def value(p, x):
        out = M._attention(p, x, cfg, jnp.bfloat16)
        return jnp.sum(out.astype(jnp.float32) * probe), out

    run = lambda: jax.jit(jax.value_and_grad(value, argnums=(0, 1), has_aux=True))(p, x)
    (_, out), (dp, dx) = run()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(M, "_use_attention_kernel", lambda length: False)
        (_, ref), (ref_dp, ref_dx) = run()
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.abs(ref).max() > 0.5 and np.abs(out - ref).max() <= 2.0 ** -7 * np.abs(ref).max()
    assert _rel(dx, ref_dx) < 0.01
    for name in ("q", "k", "v", "o", "q_norm", "k_norm"):
        assert float(jnp.abs(ref_dp[name]).max()) > 0 and _rel(dp[name], ref_dp[name]) < 0.01, name
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, length, 1, group, 64)), jnp.float32)
    k, v, weight = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                    for shape in ((1, length, 1, 64), (1, length, 1, 64), q.shape))
    cores = (functools.partial(M._kernel_core, scale=0.125), functools.partial(M._blockwise_core, scale=0.125, block=256))
    got, want = (jax.jit(jax.grad(lambda q, k, v: jnp.sum((core(q, k, v) * weight).astype(jnp.float32)), (0, 1, 2)))(
        q, k, v) for core in cores)
    assert all(_rel(g, w) < 0.01 for g, w in zip(got, want)), [_rel(g, w) for g, w in zip(got, want)]


def test_attention_and_every_gradient_by_the_fused_core_match_the_float32_reference(kernel_on_the_cpu):
    """``_attention`` whole at LFM2's form (two sequences, two key-value heads of four query heads at head size
    64, the norm of q and k over the head: head-major products, norm, rope as a product with the signed
    permutation, scale, the kernel interpreted, the output product over its head-major output), in float32
    against ``reference.attention`` a sequence: the output within two bfloat16 steps of its size, the gradients
    of the input and of every weight, the two norms' among them, within 1% in norm."""
    cfg, p, x = _attention_case(256, 4, sequences=2, kv_heads=2)
    x = x.astype(jnp.float32)
    m = dict(num_attention_heads=8, num_key_value_heads=2, norm_eps=cfg.norm_eps,
             rope_parameters={"rope_theta": cfg.rope_theta})
    probe = jnp.asarray(np.random.default_rng(1).normal(size=x.shape), jnp.float32)

    def run(operator):
        def value(p, x):
            out = operator(p, x)
            return jnp.sum(out * probe), out
        return jax.jit(jax.value_and_grad(value, argnums=(0, 1), has_aux=True))(p, x)

    (_, out), (dp, dx) = run(lambda p, x: M._attention(p, x, cfg, jnp.float32))
    with HIGHEST:
        (_, ref), (ref_dp, ref_dx) = run(lambda p, x: jnp.stack([R.attention(p, xs, m, lambda a: a) for xs in x]))
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.abs(ref).max() > 0.5 and np.abs(out - ref).max() <= 2.0 ** -7 * np.abs(ref).max()
    assert _rel(dx, ref_dx) < 0.01
    for name in ("q", "k", "v", "o", "q_norm", "k_norm"):
        assert float(jnp.abs(ref_dp[name]).max()) > 0 and _rel(dp[name], ref_dp[name]) < 0.01, name


def test_the_kernels_operands_are_head_major_from_the_products_on(monkeypatch):
    """With the kernel chosen, LFM2's operator (no scopes inside ``attention``) hands the kernel three head-major
    operands in the compute dtype, its products emit that order (the heads are in the weights' shape: no token-major
    product is reshaped by head), and there is one kernel call."""
    monkeypatch.setattr(M, "_use_attention_kernel", lambda length: True)
    cfg, p, x = _attention_case(256, 4, sequences=2, kv_heads=2)
    s, length, nkv, group, hd = 2, 256, 2, 4, 64
    def equations(jaxpr):  # nested ones too: the kernel's call sits inside the library's jit
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    jaxpr = jax.make_jaxpr(lambda p, x: M._attention(p, x, cfg, jnp.bfloat16))(p, x).jaxpr
    avals = [(eqn.primitive.name, v.aval) for eqn in equations(jaxpr) for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert not [a for name, a in avals if name == "reshape" and a.ndim >= 4 and a.shape[:2] == (s, length)], \
        "no product is re-cut by head"
    kernel = [a for name, a in avals if name == "custom_vjp_call"]
    assert len(kernel) == 1 and kernel[0].shape == (s, nkv, group, length, hd)
    head_major = [a for name, a in avals if name == "transpose" and a.shape[:2] == (s, nkv) and a.dtype == jnp.bfloat16]
    wanted = [(s, nkv, group, length, hd), (s, nkv, length, hd), (s, nkv, length, hd)]
    assert sorted(a.shape for a in head_major) == sorted(wanted * 2), "each operand once by its product, once at the kernel"


@pytest.mark.parametrize("core", ["kernel", "blockwise"])
def test_no_output_of_attention_sees_a_later_token(core, kernel_on_the_cpu, monkeypatch):
    if core == "blockwise":
        monkeypatch.setattr(M, "_use_attention_kernel", lambda length: False)
    cfg, p, x = _attention_case(512, 4, sequences=2, kv_heads=2)
    t = 300  # inside a block, not at its edge
    later = x.at[:, t + 1:].set(jnp.asarray(np.random.default_rng(3).normal(size=x[:, t + 1:].shape), x.dtype))
    run = jax.jit(lambda x: M._attention(p, x, cfg, jnp.bfloat16))
    out, out_later = np.asarray(run(x), np.float32), np.asarray(run(later), np.float32)
    np.testing.assert_array_equal(out[:, :t + 1], out_later[:, :t + 1])
    assert np.abs(out[:, t + 1:] - out_later[:, t + 1:]).max() > 0.1


def test_the_core_is_chosen_by_backend_and_length(tokens, monkeypatch):
    """The CPU takes the XLA core and says so; a TPU takes the kernel at whole
    blocks only; and the published shape, lowered for the TPU here, holds the
    kernel's custom calls under ``layer{l}/attention``, forward and backward."""
    assert jax.default_backend() == "cpu" and not M._use_attention_kernel(4096)
    assert M.Lfm2MoeModel.compiled_programs(tokens[0], **model_kwargs()).attention_kernel_layers == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert M._use_attention_kernel(4096) and M._use_attention_kernel(512) and M._use_attention_kernel(128)
    assert not M._use_attention_kernel(4096 + 512) and not M._use_attention_kernel(16) \
        and not M._use_attention_kernel(192)
    assert M._kernel_blocks(512)["block_q"] == 512 and M._kernel_blocks(4096) == M._ATTN_KERNEL_BLOCKS
    M._programs.cache_clear()
    try:
        programs = M._programs(M.Lfm2MoeConfig())
        assert programs.attention_kernel_layers == 2
        cfg = programs.config
        state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
        x = jax.ShapeDtypeStruct((cfg.n_sequences, cfg.seq_len), jnp.int32)
        text = programs.train_step.trace(
            state, x, x, jax.ShapeDtypeStruct((cfg.train_steps, cfg.batch_sequences), jnp.int32),
            jax.ShapeDtypeStruct((5,), jnp.float32), jax.ShapeDtypeStruct((), jnp.int32),
        ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        M._programs.cache_clear()
    # XLA names a custom call "<the call site's scopes>/<the kernel's own name>" (seen in the program compiled for
    # the chip: ".../jvp(layer2)/attention/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/.../pallas_call")
    names = set(re.findall(r'loc\("([^"]+)"', text))
    sites = {n for n in names if n.endswith("jit(_splash_attention)))")}
    kernels = {n for n in names if n.endswith("pallas_call") and "splash" in n}
    assert text.count("@tpu_custom_call") >= 2 and any("fwd" in n for n in kernels) and any("dkv" in n for n in kernels)
    for layer in ("layer2", "layer6"):
        for stage in (f"jvp({layer})/attention", f"rematted_computation/{layer}/attention",
                      f"transpose(jvp(jvp()))/checkpoint/{layer}/attention"):
            assert any(stage in n for n in sites), (stage, sorted(sites))
    assert len(sites) == 6
    assert all(scope_rules.classify(f"{site}/{kernel}") == ("attention", re.search(r"layer\d", site)[0])
               for site in sites for kernel in kernels)
    assert "sngqk" not in text, "the blockwise core's score product is still in the program"


def test_a_train_span_and_the_counter_say_how_many_attention_layers_ran_the_kernel(long_tokens, kernel_on_the_cpu):
    x, y = long_tokens
    m = {**MODEL, "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 1,
         "layer_types": ["full_attention", "conv"], "num_dense_layers": 1}
    programs = M.Lfm2MoeModel.compiled_programs(x, **model_kwargs(m, compute_dtype="bfloat16"))
    assert programs.attention_kernel_layers == 1
    get_registry().reset()
    sink = _Sink()
    spans.set_run_sink(sink)
    spans.enable()
    try:
        for individual in range(2):
            loss = M._score_one(programs, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32), M.gene_vector(GENES),
                                jnp.asarray(x), jnp.asarray(y), jnp.asarray([[0, 1], [2, 3], [0, 2]], np.int32),
                                [jnp.asarray([4, 5])], [np.int32(s) for s in range(3)], individual)
    finally:
        spans.disable()
        spans.set_run_sink(None)
    assert 0 < loss < np.log(64) + 0.5
    trained = [r["attrs"] for r in sink.records if r["type"] == "span" and r["attrs"].get("steps") == 3]
    assert [a["attention_kernel_layer_steps"] for a in trained] == [3, 3]  # 1 attention layer x 3 steps, twice
    assert get_registry().counter("attention_kernel_layer_steps_total", mask="causal").value == 6


def _pool(n=3):
    rng = np.random.default_rng(8)
    return [lfm2_moe_genome().default()] + [lfm2_moe_genome().sample(rng) for _ in range(n - 1)]


def test_fitness_is_a_function_of_the_genome_in_any_position_and_with_telemetry_on_or_off(tokens):
    x, y = tokens
    kw = model_kwargs(seed=3)
    pool = _pool()
    base = M.Lfm2MoeModel.cross_validate_population(x, y, pool, **kw)
    assert base.dtype == np.float32 and np.all(base < 0) and len(set(base.tolist())) == len(pool)
    for order in ([2, 0, 1], [1, 2, 0], [1]):
        again = M.Lfm2MoeModel.cross_validate_population(x, y, [pool[i] for i in order], **kw)
        np.testing.assert_array_equal(again, base[order])
    assert M.Lfm2MoeModel(x, y, pool[1], **kw).cross_validate() == base[1]
    get_registry().reset()
    sink = _Sink()
    records = sink.records
    spans.set_run_sink(sink)
    spans.enable()
    try:
        traced = M.Lfm2MoeModel.cross_validate_population(x, y, pool, **kw)
    finally:
        spans.disable()
        spans.set_run_sink(None)
    np.testing.assert_array_equal(traced, base)
    kinds = [r["kind"] for r in records if r["type"] == "span"]
    assert kinds.count("cv_call") == 1 and kinds.count("fetch") == len(pool)
    device = [r for r in records if r["type"] == "span" and "individual" in r.get("attrs", {})]
    assert {r["kind"] for r in device} <= {"compile", "init_params", "train", "eval", "fetch"}
    fetched = [r["attrs"] for r in records if r["type"] == "span" and r["kind"] == "fetch"]
    rows = {(c["labels"]["layer"], c["labels"]["expert"]): c["value"]
            for c in get_registry().snapshot()["counters"] if c["name"] == "expert_rows"}
    assert set(rows) == {(str(l), str(e)) for l in (1, 2) for e in (2, 3)}
    assert sum(rows.values()) == sum(sum(map(sum, a["expert_rows"])) for a in fetched) > 0
    assert get_registry().counter("dropped_assignments_total").value == 0
    assert get_registry().counter("row_buffer_wide_total").value == 0 == sum(a["wide_buffer"] for a in fetched)
    trained = [r["attrs"] for r in device if r["attrs"].get("steps")]
    assert len(trained) == len(pool) and all(a["attention_kernel_layer_steps"] == 0 for a in trained)
    assert get_registry().counter("attention_kernel_layer_steps_total", mask="causal").value == 0
    assert M.Lfm2MoeModel.cross_validate_population(x, y, [], **kw).shape == (0,)
    other_seed = M.Lfm2MoeModel.cross_validate_population(x, y, pool[:1], **{**kw, "seed": 4})
    assert other_seed[0] != base[0]


def test_genome_individual_population_and_two_generations(tokens):
    x, y = tokens
    spec = lfm2_moe_genome()
    assert spec.names == list(M.GENE_NAMES)
    assert spec.default() == dict(log10_lr=-3.5, warmup_frac=0.25, weight_decay=0.1, beta2=0.95, bias_step=0.001)
    for gene, (lo, hi) in zip(spec.genes, [(-4, -2.5), (0, 0.5), (0, 0.2), (0.9, 0.999), (0, 0.01)]):
        assert (gene.minimum, gene.maximum) == (lo, hi)
    assert Lfm2MoeIndividual.model_cls is M.Lfm2MoeModel and Lfm2MoeIndividual.uses_jax
    assert Lfm2MoeIndividual.fitness_backend() == "Lfm2MoeModel"
    calls = []

    class Counting(M.Lfm2MoeModel):
        @classmethod
        def cross_validate_population(cls, x_train, y_train, genomes, **config):
            calls.append(len(genomes))
            return super().cross_validate_population(x_train, y_train, genomes, **config)

    class Species(Lfm2MoeIndividual):
        model_cls = Counting

    pop = Population(Species, x, y, size=3, seed=0, additional_parameters=model_kwargs(seed=1))
    ga = GeneticAlgorithm(pop, seed=0)
    ga.run(2)
    assert calls and sum(calls) >= 3, "Population.evaluate must reach cross_validate_population"
    best = ga.population.get_fittest()
    assert best.get_fitness() < 0 and best.get_fitness() == max(ga.population.get_fitnesses())
    single = Lfm2MoeIndividual(x, y, genes=best.get_genes(), additional_parameters=model_kwargs(seed=1))
    assert single.get_fitness() == pytest.approx(best.get_fitness(), abs=0)


def test_the_worker_resolves_the_species():
    from gentun_tpu.distributed.worker import _species

    assert _species("lfm2-moe") is Lfm2MoeIndividual
    with pytest.raises(SystemExit, match="lfm2-moe"):
        _species("no-such-species")


@pytest.mark.parametrize("bad,why", [
    (dict(held_experts=(6, 9)), "held_experts"),
    (dict(num_dense_layers=3), "routed layer"),
    (dict(eval_sequences=3), "held-out"),
    (dict(vocab_size=32), "held slice"),
    (dict(layer_types=("conv", "mamba", "conv")), "layer_types"),
])
def test_a_configuration_that_cannot_run_is_refused_before_anything_compiles(tokens, bad, why):
    with pytest.raises(ValueError, match=why):
        M.Lfm2MoeModel.cross_validate_population(tokens[0], tokens[1], _pool(1), **model_kwargs(**bad))


def test_the_published_cut_is_one_individual_wide_by_arithmetic():
    need = M.training_bytes(M.Lfm2MoeConfig())
    assert 0.64e9 < need["params"] < 0.66e9 and need["state"] == 16 * need["params"]
    assert 16e9 / 2 < need["total"] < 16e9, "one individual fits a 16 GB chip, two do not"
    assert M.PROGRAM_WIDTH == 1


@pytest.mark.parametrize("op_name,klass", [
    ("jit(lm_train_step)/jvp(layer2)/moe/experts/pallas_call", "expert_mm"),
    ("jit(lm_train_step)/transpose(jvp(layer2))/moe/experts/mul", "expert_mm"),
    ("jit(lm_train_step)/jvp(layer2)/cond/branch_0_fun/moe/experts/jit(gmm)/pallas_call", "expert_mm"),
    ("jit(lm_train_step)/transpose(jvp(jvp()))/checkpoint/layer3/cond/branch_1_fun/transpose(jvp(moe))/experts/"
     "jit(tgmm)/pallas_call", "expert_mm"),
    ("jit(lm_train_step)/transpose(jvp(layer2))/cond/branch_1_fun/moe/combine/scatter-add", "moe_route"),
    ("jit(lm_eval)/layer7/cond/branch_0_fun/moe/dispatch/jit(_take)/gather", "moe_route"),
    ("jit(lm_train_step)/jvp(layer5)/moe/dispatch/jit(argsort)/sort", "moe_route"),
    ("jit(lm_train_step)/checkpoint/rematted_computation/layer3/moe/router/dot_general", "moe_route"),
    ("jit(lm_eval)/layer6/attention/checkpoint/sngqk,sknd->sqngd/dot_general", "attention"),
    ("jit(lm_train_step)/jvp(layer0)/conv_op/dot_general", "short_conv"),
    ("jit(lm_train_step)/transpose(jvp(layer0))/dense_ffn/dot_general", "dense_ffn"),
    ("jit(lm_train_step)/jvp(head)/slh,vh->slv/dot_general", "head_loss"),
    ("jit(lm_train_step)/jvp(embed)/jit(_take)/gather", "head_loss"),
    ("jit(lm_train_step)/optimizer/sqrt", "optimizer"),
    ("jit(lm_train_step)/bias_update/sign", "optimizer"),
    ("jit(lm_train_step)/jvp(layer3)/rsqrt", "rest"),
    ("jit(lm_init)/jit(_normal)/threefry2x32", "rest"),
    ("", "unattributed"),
])
def test_scope_rules_place_an_op_by_its_scopes(op_name, klass):
    assert scope_rules.classify(op_name)[0] == klass and klass in scope_rules.CLASSES


def _lowered_train_step(programs, x, y) -> str:
    state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    return programs.train_step.lower(state, x, y, np.zeros((3, 2), np.int32), np.zeros(5, np.float32),
                                     np.int32(0)).as_text(debug_info=True)


def test_the_lowered_train_step_carries_every_scope(tokens, long_tokens):
    programs = M.Lfm2MoeModel.compiled_programs(tokens[0], **model_kwargs(layer_ids=(0, 2, 3)))
    text = _lowered_train_step(programs, *tokens)
    for scope in ("embed", "layer0", "layer2", "layer3", "conv_op", "attention", "dense_ffn", "moe/router",
                  "moe/dispatch", "moe/experts", "moe/combine", "head", "loss", "optimizer", "bias_update"):
        assert scope in text, scope
    # with the ladder's three heights (a ``switch``, forward and backward) every op of the expert layer keeps its class
    two = _lowered_train_step(M.Lfm2MoeModel.compiled_programs(long_tokens[0], **model_kwargs(LADDER)),
                              *long_tokens)
    names, one_height = (set(re.findall(r'loc\("([^"]+)"', t)) for t in (two, text))
    branches = {n for n in names if "/cond/branch_" in n}
    assert {n.split("/cond/")[1].split("/")[0] for n in branches} == {f"branch_{i}_fun" for i in range(3)}
    assert any("transpose" in n for n in branches) and len(branches) > 100
    for part in ("dispatch", "experts", "combine"):
        assert any(scope_rules.classify(n) == ("expert_mm" if part == "experts" else "moe_route", part)
                   for n in branches), part
    assert all(scope_rules.classify(n)[0] == "expert_mm" for n in names | one_height if "experts" in n)
    other = lambda found: {n.rsplit("/", 1)[-1] for n in found if scope_rules.classify(n) == ("moe_route", "other")}
    assert other(names) <= other(one_height)
    assert not [n for n in branches if scope_rules.classify(n)[0] not in ("expert_mm", "moe_route")]


def test_executed_flops_of_the_grouped_products_on_a_recorded_load():
    """A load recorded from a run (rows per routed layer and held expert, 8
    steps): the count is 3 products of 2 x hidden x width a row and pass, and
    against any time the chip could have taken it stays under the peak."""
    m = dict(hidden_size=2048, moe_intermediate_size=1536, held_experts=[0, 8])
    recorded = np.array([[1511, 702, 1210, 988, 640, 1333, 871, 1009]] * 6) * 8  # six routed layers, 8 steps
    rows = float(recorded.sum())
    assert flops.expert_mm_flops(m, rows, 1) == rows * 3 * 2 * 2048 * 1536
    assert flops.expert_mm_flops(m, rows, flops.TRAIN_PASSES) == 4 * flops.expert_mm_flops(m, rows, 1)
    peak, bandwidth = 197e12, 819e9
    least = max(flops.expert_mm_flops(m, rows, 4) / peak, flops.expert_mm_bytes(m, rows, 4, 48) / bandwidth)
    assert least == flops.expert_mm_flops(m, rows, 4) / peak  # compute-bound at ~1,000 rows an expert
    assert 100.0 * least / (1.25 * least) < 100.0
    per = flops.forward_flops_per_token({**MODEL, "hidden_size": 2048, "intermediate_size": 11776, "num_experts": 64,
                                         "num_attention_heads": 32, "num_key_value_heads": 8, "vocab_size": 8192,
                                         "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                                                         "full_attention", "conv"]}, 4096, 512)
    total = per["linear"] + per["attention_core"] + per["head"] + 6 * 4 / 8 * 3 * 2 * 2048 * 1536
    assert 0.40e9 < total < 0.50e9  # the issue's ~0.48 GFLOP a token forward, attention by causal blocks


@pytest.fixture()
def layer_metric():
    """A reader of ``benchmark/layer_metrics/`` by name, loaded as ``run.py``
    loads it (the family's directory and the harness's on ``sys.path``)."""
    bench = os.path.dirname(os.path.dirname(FAMILY))
    names = ("lm_spans", "scope_rules", "scope_reduce", "spanlib", "trace_reduce")
    before = {n: sys.modules.pop(n, None) for n in names}
    sys.path[:0] = [FAMILY, bench]
    try:
        yield lambda name: _load(os.path.join("..", "..", "layer_metrics", name))
    finally:
        del sys.path[:2]
        for n in names:
            sys.modules.pop(n, None)
            if before[n] is not None:
                sys.modules[n] = before[n]


def _span(kind, t, attrs):
    return {"type": "span", "kind": kind, "t_wall": t, "dur_s": 0.001, "attrs": attrs}


def test_the_expert_load_reader_reads_the_windows_fetch_spans_and_not_set_ups(layer_metric):
    """``lm_expert_load_max_over_mean`` is a reading of the window: the warm-up
    call's individuals (before the window) and spans without ``individual`` stay out."""
    reader = layer_metric("lm_expert_load_max_over_mean")
    run = {"window": (10.0, 20.0), "records": [
        _span("fetch", 5.0, {"individual": 0, "expert_rows": [[9000, 1], [1, 1]]}),  # set-up's warm-up call
        _span("fetch", 11.0, {"individual": 0, "expert_rows": [[10, 30], [20, 20]]}),
        _span("fetch", 12.0, {"individual": 1, "expert_rows": [[30, 50], [20, 20]]}),
        _span("fetch", 13.0, {"other": 1, "expert_rows": [[7000, 1], [1, 1]]})]}
    assert reader.read(run) == pytest.approx(80 * 4 / 200)
    assert reader.read({"window": (10.0, 20.0), "records": run["records"][:1]}) is None


def test_the_row_buffer_reader_divides_the_rows_the_heights_ran_by_the_rows_routed_and_the_parent_reads_nothing(
        layer_metric):
    reader = layer_metric("lm_row_buffer_rows_per_routed_row")
    routed_ladder.assert_the_reader_divides_the_rows_run_by_the_rows_routed(reader)


def test_the_kernel_reader_averages_the_windows_train_spans_and_a_program_without_the_attribute_reads_nothing(
        layer_metric):
    reader = layer_metric("lm_attention_kernel_layer_steps")
    train = lambda t, **attrs: _span("train", t, {"individual": 0, "steps": 8, **attrs})
    window = {"window": (10.0, 20.0)}
    records = [train(5.0, attention_kernel_layer_steps=0),  # set-up's warm-up call
               train(11.0, attention_kernel_layer_steps=16), train(12.0, attention_kernel_layer_steps=16),
               _span("train", 13.0, {"fold": 0, "attention_kernel_layer_steps": 99})]  # no span of this family
    assert reader.read({**window, "records": records}) == 16
    assert reader.read({**window, "records": [train(11.0, attention_kernel_layer_steps=0)]}) == 0
    assert reader.read({**window, "records": [train(11.0)]}) is None  # the parent's program
    assert reader.read({**window, "records": records[:1]}) is None


@pytest.fixture()
def family():
    """``benchmark/families/lfm2_moe/family.py``, loaded as ``run.py`` loads it
    (its directory first on ``sys.path``), and unloaded again."""
    names = ("family", "correct", "reference")
    before = {n: sys.modules.pop(n, None) for n in names}
    sys.path.insert(0, FAMILY)
    try:
        yield _load("family")
    finally:
        sys.path.remove(FAMILY)
        for n in names:
            sys.modules.pop(n, None)
            if before[n] is not None:
                sys.modules[n] = before[n]


def _cell_files():
    import json

    bench = os.path.dirname(os.path.dirname(FAMILY))
    with open(os.path.join(bench, "configs", "lfm2_24b_a2b_ep8.json")) as f, \
            open(os.path.join(bench, "traffic", "lmpopeval_fresh.json")) as g:
        return json.load(f), json.load(g)


def test_the_cells_pool_holds_the_defaults_and_no_recipe_that_diverges(family):
    """A recipe hotter than the mix's ceiling diverges inside its few steps at
    the published width, and its rows and loss then follow the seed."""
    _, mix = _cell_files()
    ceiling = mix["pool_log10_lr_max"]
    pool = family.make_pool(4, [mix["pool_seed"]], ceiling)
    assert pool[0] == lfm2_moe_genome().default() and pool[0]["log10_lr"] <= ceiling
    assert len(pool) == 4 and all(r["log10_lr"] <= ceiling for r in pool)
    assert pool == family.make_pool(4, [mix["pool_seed"]], ceiling)
    spec = lfm2_moe_genome()
    assert all(g.minimum <= r[g.name] <= g.maximum for r in pool for g in spec.genes)
    assert any(r["log10_lr"] > ceiling for r in family.make_pool(12, [mix["pool_seed"]], spec.genes[0].maximum))


def test_the_cells_tokens_have_many_effective_ids_whatever_the_seed(family):
    """The work of a routed layer follows which experts the common ids draw:
    with few effective ids (1 / sum p^2) it follows the seed."""
    config, _ = _cell_files()
    for seed in (3, 2**31 + 5):
        tokens = family.markov_tokens(config["data"], config["vocab_size"], 8, config["data"]["seq_len"], seed)
        assert tokens.shape == (8, config["data"]["seq_len"] + 1) and tokens.min() >= 0
        share = np.bincount(tokens.ravel(), minlength=config["vocab_size"]) / tokens.size
        assert share.max() < 0.03 and 1.0 / np.sum(share**2) > 500
        # half the steps follow a fixed successor, so the chain can be learned
        nxt = {}
        follows = sum(nxt.setdefault(a, b) == b for a, b in zip(tokens[:, :-1].ravel(), tokens[:, 1:].ravel()))
        assert follows / tokens[:, 1:].size > 0.4
