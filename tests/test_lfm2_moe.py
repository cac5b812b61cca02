"""What is LFM2-MoE's own among the routed family's tests (``models/lfm2_moe.py`` against
``benchmark/families/lfm2_moe/reference.py``, at small sizes on the CPU); what every architecture is held to
(logits, loss and gradients, two train steps, the shares, the row buffer's ladder, refusals, the species) is in
``test_routed_family*.py`` under ``lfm2_moe-`` ids.

Here: a forced router keeps every assignment; a forced bias takes the wide row buffer and the spans count it;
the fused attention kernel (the TPU's core) in Pallas' interpret mode against the blockwise XLA core, the one every
other test of this file takes; fitness as a pure function of genome, configuration and seed in any position of any
call, telemetry on or off; the scopes of the lowered train step; ``flops.py``'s counts; the family's readers, pool
and tokens.
"""

from __future__ import annotations

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu import lfm2_moe_genome
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry.registry import get_registry
from routed_family import HIGHEST, kernel_on_the_cpu  # noqa: F401  (the fixture)

A = F.ARCHS["lfm2_moe"]
R, flops, scope_rules = A.R, A.flops, A.scope_rules
MODEL, GENES = A.model, A.genes
LADDER, LADDER_HEIGHTS = F.LADDERS["lfm2_moe"][:2]
one_layer = F.lfm2_one_layer
rel = F.rel


@pytest.fixture(scope="module")
def tokens():
    return A.tokens


@pytest.fixture(scope="module")
def long_tokens():
    return F.long_tokens()


def test_no_assignment_is_dropped_when_every_token_goes_to_one_held_expert(tokens):
    m = one_layer("conv", "moe")
    cfg = A.config_of(m)
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


def test_a_train_step_on_a_forced_bias_counts_the_layers_that_took_the_wide_buffer(long_tokens):
    x, y = long_tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **A.model_kwargs(LADDER))
    cfg = programs.config
    assert M._row_buffer_heights(cfg, cfg.tokens_per_step) == LADDER_HEIGHTS
    # the published cut: 20 and 44 tiles under the worst case (44 tiles are the 2.75 shares of PR 29)
    assert M._row_buffer_heights(M.Lfm2MoeConfig(), 16384) == (10240, 22528, 65536)
    w = R.seeded_weights(LADDER, 5)
    rows = np.array([[0, 1], [2, 3], [0, 2]], np.int32)
    forced = np.zeros((1, 8), np.float32)
    forced[0, 2:4] = 10.0  # both choices of every token go to the two held experts: 2,048 rows
    with HIGHEST:
        state, losses, loads = F.program_steps(programs, w, x, y, rows, 2, GENES, forced)
        ref = R.train(LADDER, w, [(x[r], y[r]) for r in rows[:2]], GENES, bias=forced)
        calm, _, calm_loads = F.program_steps(programs, w, x, y, rows, 2, GENES, np.zeros((1, 8), np.float32))
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
        with F.traced() as records:
            F.score_one(scored, x, y, GENES)
        (fetched,) = F.span_attrs(records, "fetch")
        heights, taken = zip(*fetched["row_buffer_heights"])
        assert heights == LADDER_HEIGHTS and sum(taken) == 3 and fetched["dropped"] == 0  # 3 steps x 1 routed layer
        assert by_height is None or list(taken) == by_height
        assert fetched["wide_buffer"] == taken[-1] == get_registry().counter("row_buffer_wide_total").value
        assert [get_registry().counter("row_buffer_height_total", rows=str(h)).value for h in heights] == list(taken)
    assert taken[-1] == 0 and fetched["wide_buffer"] == 0  # the model's own start routes under the worst case


# -- the fused attention kernel (PR 31) --------------------------------------------------------------


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
    assert rel(dx, ref_dx) < 0.01
    for name in ("q", "k", "v", "o", "q_norm", "k_norm"):
        assert float(jnp.abs(ref_dp[name]).max()) > 0 and rel(dp[name], ref_dp[name]) < 0.01, name
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, length, 1, group, 64)), jnp.float32)
    k, v, weight = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                    for shape in ((1, length, 1, 64), (1, length, 1, 64), q.shape))
    cores = (functools.partial(M._kernel_core, scale=0.125), functools.partial(M._blockwise_core, scale=0.125, block=256))
    got, want = (jax.jit(jax.grad(lambda q, k, v: jnp.sum((core(q, k, v) * weight).astype(jnp.float32)), (0, 1, 2)))(
        q, k, v) for core in cores)
    assert all(rel(g, w) < 0.01 for g, w in zip(got, want)), [rel(g, w) for g, w in zip(got, want)]


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
    assert rel(dx, ref_dx) < 0.01
    for name in ("q", "k", "v", "o", "q_norm", "k_norm"):
        assert float(jnp.abs(ref_dp[name]).max()) > 0 and rel(dp[name], ref_dp[name]) < 0.01, name


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
    assert M.Lfm2MoeModel.compiled_programs(tokens[0], **A.model_kwargs()).attention_kernel_layers == 0
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
    programs = M.Lfm2MoeModel.compiled_programs(x, **A.model_kwargs(m, compute_dtype="bfloat16"))
    assert programs.attention_kernel_layers == 1
    with F.traced() as records:
        for individual in range(2):
            loss = F.score_one(programs, x, y, GENES, individual)
    assert 0 < loss < np.log(64) + 0.5
    trained = F.span_attrs(records, steps=3)
    assert [a["attention_kernel_layer_steps"] for a in trained] == [3, 3]  # 1 attention layer x 3 steps, twice
    assert get_registry().counter("attention_kernel_layer_steps_total", mask="causal").value == 6


def _pool(n=3):
    rng = np.random.default_rng(8)
    return [lfm2_moe_genome().default()] + [lfm2_moe_genome().sample(rng) for _ in range(n - 1)]


def test_fitness_is_a_function_of_the_genome_in_any_position_and_with_telemetry_on_or_off(tokens):
    x, y = tokens
    kw = A.model_kwargs(seed=3)
    pool = _pool()
    base = M.Lfm2MoeModel.cross_validate_population(x, y, pool, **kw)
    assert base.dtype == np.float32 and np.all(base < 0) and len(set(base.tolist())) == len(pool)
    for order in ([2, 0, 1], [1, 2, 0], [1]):
        again = M.Lfm2MoeModel.cross_validate_population(x, y, [pool[i] for i in order], **kw)
        np.testing.assert_array_equal(again, base[order])
    assert M.Lfm2MoeModel(x, y, pool[1], **kw).cross_validate() == base[1]
    with F.traced() as records:
        traced = M.Lfm2MoeModel.cross_validate_population(x, y, pool, **kw)
    np.testing.assert_array_equal(traced, base)
    kinds = [r["kind"] for r in records if r["type"] == "span"]
    assert kinds.count("cv_call") == 1 and kinds.count("fetch") == len(pool)
    device = [r for r in records if r["type"] == "span" and "individual" in r.get("attrs", {})]
    assert {r["kind"] for r in device} <= {"compile", "init_params", "train", "eval", "fetch"}
    fetched = F.span_attrs(records, "fetch")
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


def test_the_published_cut_is_one_individual_wide_by_arithmetic():
    need = M.training_bytes(M.Lfm2MoeConfig())
    assert 0.64e9 < need["params"] < 0.66e9 and need["state"] == 16 * need["params"]
    assert 16e9 / 2 < need["total"] < 16e9, "one individual fits a 16 GB chip, two do not"
    assert M.PROGRAM_WIDTH == 1


def _lowered_train_step(programs, x, y) -> str:
    state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    return programs.train_step.lower(state, x, y, np.zeros((3, 2), np.int32), np.zeros(5, np.float32),
                                     np.int32(0)).as_text(debug_info=True)


def test_the_lowered_train_step_carries_every_scope(tokens, long_tokens):
    programs = M.Lfm2MoeModel.compiled_programs(tokens[0], **A.model_kwargs(layer_ids=(0, 2, 3)))
    text = _lowered_train_step(programs, *tokens)
    for scope in ("embed", "layer0", "layer2", "layer3", "conv_op", "attention", "dense_ffn", "moe/router",
                  "moe/dispatch", "moe/experts", "moe/combine", "head", "loss", "optimizer", "bias_update"):
        assert scope in text, scope
    # with the ladder's three heights (a ``switch``, forward and backward) every op of the expert layer keeps its class
    two = _lowered_train_step(M.Lfm2MoeModel.compiled_programs(long_tokens[0], **A.model_kwargs(LADDER)),
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
    """A reader of ``benchmark/layer_metrics/`` by name, loaded as ``run.py`` loads it."""
    with F.as_run_py_loads(A.family) as load:
        yield lambda name: load(f"layer_metrics/{name}")


_span = F.span


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
    """``benchmark/families/lfm2_moe/family.py``, loaded as ``run.py`` loads it, and unloaded again."""
    with F.as_run_py_loads(A.family) as load:
        yield load("family")


def _cell_files():
    return F.config_file("lfm2_24b_a2b_ep8"), F.traffic_mix()


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
