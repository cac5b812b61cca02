"""What is DeepSeek-V2-Lite's own among the routed family's tests (the second architecture through
``models/lfm2_moe.py``, against ``benchmark/families/deepseek_v2/reference.py``, at small sizes on the CPU); what
every architecture is held to (logits, loss with the balance term and gradients, two train steps, the shares with
the shared experts counted once, the row buffer at top-6, refusals, the species, the scope rules) is in
``test_routed_family*.py`` under ``deepseek_v2-`` ids.

Here: ``k_pe`` is one head, causality in both cores, the fused core at 192 / 128 in Pallas' interpret mode, YaRN's
frequencies by hand, the router's rule, where the balance term's gradient goes, the grouped products' tiles, the
published cut's arithmetic, the spans, the scopes of the lowered train step, the counts of ``flops.py`` and the
readers of the per-layer metrics.
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
from routed_family import HIGHEST, STD, YARN, kernel_on_the_cpu  # noqa: F401  (the fixture)

A = F.ARCHS["deepseek_v2"]
R, flops, scope_rules = A.R, A.flops, A.scope_rules
MODEL, GENES = A.model, A.genes


@pytest.fixture(scope="module")
def tokens():
    return A.tokens


# -- latent attention ---------------------------------------------------------------------------------


def _latent_case(length: int, heads: int, sequences: int = 1, nope: int = 128, rope: int = 64, vd: int = 128,
                 rank: int = 64, hidden: int = 128):
    """(configuration, latent-attention weights, input) at the published head sizes by default."""
    cfg = M.Lfm2MoeConfig(hidden_size=hidden, num_attention_heads=heads, kv_lora_rank=rank, qk_nope_head_dim=nope,
                          qk_rope_head_dim=rope, v_head_dim=vd, rope_scaling=tuple(sorted(YARN.items())),
                          rope_theta=10000.0, norm_eps=1e-6, seq_len=length, attn_block=256,
                          layer_types=("latent_attention",), layer_ids=(0,), num_dense_layers=0)
    rng = np.random.default_rng(length + heads)
    shapes = M.param_shapes(cfg)["layers"][0]["latent"]
    p = {name: jnp.asarray(1.0 + 0.1 * rng.normal(size=shape) if "norm" in name
                           else rng.normal(size=shape) / np.sqrt(shape[0]), jnp.float32)
         for name, shape in shapes.items()}
    x = jnp.asarray(rng.normal(size=(sequences, length, hidden)), jnp.bfloat16)
    return cfg, p, x


@pytest.mark.parametrize("core", ["blockwise", "kernel"])
def test_k_pe_is_one_head_that_every_query_head_shares(core, request):
    """``W_kva``'s rope columns make one key head; every one of the query heads
    reads it: the gradient that reaches those columns through head ``h`` alone
    (the other heads' rows of ``W_o`` zeroed) is nonzero for each ``h``, and the
    heads' gradients add up to the whole one.  By the fused core too (its
    kernels interpreted, at the published head sizes, which it needs): there the
    key's rope part is broadcast where the operand is assembled, and what comes
    back is the sum of ``dk``'s rope columns over the heads."""
    if core == "kernel":
        request.getfixturevalue("kernel_on_the_cpu")
        heads, length, sizes = 3, 128, {}
    else:
        heads, length, sizes = 4, 32, dict(nope=8, rope=4, vd=8)
    cfg, p, x = _latent_case(length, heads, rank=16, hidden=32, **sizes)
    x = x.astype(jnp.float32)
    probe = jnp.asarray(np.random.default_rng(1).normal(size=x.shape), jnp.float32)

    def through(heads_mask):
        o = p["o"] * jnp.repeat(heads_mask, cfg.v_head_dim)[:, None]  # head h's values reach the output times mask[h]

        def value(kva):
            return jnp.sum(M._latent_attention({**p, "kva": kva, "o": o}, x, cfg, jnp.float32) * probe)
        return jax.grad(value)(p["kva"])[:, cfg.kv_lora_rank:]  # the rope columns

    with HIGHEST:
        whole = through(jnp.ones(heads))
        per_head = [through(jnp.eye(heads)[h]) for h in range(heads)]
    assert p["kva"].shape == (32, 16 + cfg.qk_rope_head_dim), "one rope head, not one a query head"
    largest = float(jnp.abs(whole).max())
    assert all(float(jnp.abs(g).max()) > 1e-3 * largest for g in per_head)
    np.testing.assert_allclose(sum(per_head), whole, rtol=1e-4, atol=1e-5 * largest)


LATENT_WEIGHTS = ("q", "kva", "kv_norm", "kvb", "o")


def test_the_fused_core_is_the_blockwise_core_to_bfloat16_at_192_and_128(kernel_on_the_cpu):
    """One ``_latent_attention`` call by both cores at the published head sizes
    (q and k of 128 + 64, padded to 256 for the kernel; v of 128), products in
    bfloat16: the output within two bfloat16 steps of its size, the gradients of
    the input and of every projection within 1% in norm."""
    cfg, p, x = _latent_case(512, 2)
    operator = lambda p, x: M._latent_attention(p, x, cfg, jnp.bfloat16)
    F.assert_within_bfloat16(F.value_and_gradients(operator, p, x), F.by_the_blockwise_core(operator, p, x), LATENT_WEIGHTS)


def _reference_latent(p, x, cfg):
    """``reference.py::latent_attention`` a sequence, float32, on the operator's own weights."""
    m = dict(num_attention_heads=cfg.num_attention_heads, kv_lora_rank=cfg.kv_lora_rank,
             qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
             rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta, rope_scaling=YARN)
    return jnp.stack([R.latent_attention(p, xs, m, lambda a: a) for xs in x])


@pytest.mark.parametrize("against", ["blockwise-bfloat16", "reference-float32"])
def test_latent_attention_and_every_gradient_by_the_fused_core(against, kernel_on_the_cpu):
    """The operator whole (two sequences, three heads of 128 + 64 / 128: the
    column blocks of ``W_q`` and ``W_kvb``, the head-major products, the
    assembly of the kernel's operands and their transposes back) with the fused
    core interpreted: in bfloat16 against the same call by the blockwise core,
    in float32 against the plain reference, at the tolerances of the test above."""
    cfg, p, x = _latent_case(256, 3, sequences=2)
    dtype = jnp.bfloat16 if against == "blockwise-bfloat16" else jnp.float32
    x = x.astype(dtype)
    operator = lambda p, x: M._latent_attention(p, x, cfg, dtype)
    got = F.value_and_gradients(operator, p, x)
    if against == "blockwise-bfloat16":
        want = F.by_the_blockwise_core(operator, p, x)
    else:
        with HIGHEST:
            want = F.value_and_gradients(lambda p, x: _reference_latent(p, x, cfg), p, x)
    F.assert_within_bfloat16(got, want, LATENT_WEIGHTS)


def test_what_reaches_the_fused_core_is_assembled_once_in_the_compute_dtype(monkeypatch):
    """With the kernel chosen, the traced operator has no float32 array of
    tokens x heads x (nope + rope) elements or more, and no copy of ``k_pe`` a
    head, outside the ``core`` scope, where the kernel's operands are assembled
    (there XLA fuses them into the pass that writes the operand: PERF.md, PR
    33); and the kernel's three operands are head-major, 256 / 256 / 128 wide."""
    monkeypatch.setattr(M, "_use_attention_kernel", lambda length: True)
    cfg, p, x = _latent_case(256, 3, sequences=2)
    s, length, heads, rope = 2, 256, 3, cfg.qk_rope_head_dim
    wide = s * length * heads * (cfg.qk_nope_head_dim + rope)
    traced = list(F.equations(jax.make_jaxpr(lambda p, x: M._latent_attention(p, x, cfg, jnp.bfloat16))(p, x).jaxpr))
    assert any("core" in scope.split("/") for _, scope, _ in traced)
    outside = [(name, scope, aval) for name, scope, avals in traced for aval in avals
               if "core" not in scope.split("/") and hasattr(aval, "shape")]
    assert not [(n, sc, a) for n, sc, a in outside if a.dtype == jnp.float32 and a.size >= wide]
    assert not [(n, sc, a) for n, sc, a in outside if a.shape[:3] == (s, length, heads) and a.shape[-1] == rope
                and n == "broadcast_in_dim"]
    kernel = [avals for name, scope, avals in traced if name == "custom_vjp_call"]
    assert len(kernel) == 1 and kernel[0][0].shape == (s, heads, 1, length, cfg.v_head_dim)
    operands = [a for name, scope, avals in traced if name == "transpose" and "core" in scope.split("/") for a in avals]
    assert sorted(a.shape for a in operands if a.shape[:2] == (s, heads)) == sorted(
        [(s, heads, 1, length, 256), (s, heads, length, 256), (s, heads, length, cfg.v_head_dim)])
    assert all(a.dtype == jnp.bfloat16 for a in operands)


@pytest.mark.parametrize("core", ["kernel", "blockwise"])
def test_no_output_of_latent_attention_sees_a_later_token(core, kernel_on_the_cpu, monkeypatch):
    if core == "blockwise":
        monkeypatch.setattr(M, "_use_attention_kernel", lambda length: False)
    cfg, p, x = _latent_case(512, 2, sequences=2)
    t = 300  # inside a block, not at its edge
    later = x.at[:, t + 1:].set(jnp.asarray(np.random.default_rng(3).normal(size=x[:, t + 1:].shape), x.dtype))
    run = jax.jit(lambda x: M._latent_attention(p, x, cfg, jnp.bfloat16))
    out, out_later = np.asarray(run(x), np.float32), np.asarray(run(later), np.float32)
    np.testing.assert_array_equal(out[:, :t + 1], out_later[:, :t + 1])
    assert np.abs(out[:, t + 1:] - out_later[:, t + 1:]).max() > 0.1


def test_yarn_frequencies_and_the_softmax_scale_against_numbers_worked_by_hand():
    """Published settings: rope size 64, theta 10000, factor 40, beta_fast 32, beta_slow 1, original 4096.
    d(b) = 64 ln(4096 / (2 pi b)) / (2 ln 10000): d(32) = 10.47 -> low 10; d(1) = 22.51 -> high 23.  So pairs
    0-10 keep theta^(-2i/64), pairs 23-31 are divided by 40, and pair i between blends with g = 1 - (i - 10) / 13."""
    inv = M.yarn_inv_freq(64, 10000.0, YARN)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,) and inv.dtype == np.float32
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-6)
    g = 1.0 - (16 - 10) / 13.0
    np.testing.assert_allclose(inv[16], (1 - g) * 0.01 / 40.0 + g * 0.01, rtol=1e-6)  # pair 16: theta^(-1/2) = 0.01
    np.testing.assert_allclose(inv, R.yarn_frequencies(64, 10000.0, YARN), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert m == pytest.approx(1.2608, abs=5e-5) and M.yarn_mscale(40, 0.707) == pytest.approx(m)
    cfg = _latent_case(128, 2)[0]
    assert M.latent_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4) \
        and R.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": YARN}) \
        == pytest.approx(M.latent_softmax_scale(cfg))
    # cos and sin carry mscale / mscale_all_dim: 1 as published, not 1 where the two differ
    x = jnp.ones((1, 4, 1, 64), jnp.float32)
    same, other = M._rope(x, 10000.0, YARN), M._rope(x, 10000.0, {**YARN, "mscale": 1.0})
    np.testing.assert_allclose(same[0, 0], 1.0)  # position 0: no rotation, amplitude 1
    np.testing.assert_allclose(other[0, 0], M.yarn_mscale(40, 1.0) / m, rtol=1e-6)


# -- the router, the balance term, the row buffer ---------------------------------------------------------


def _moe_case(tokens, k=6, experts=16, held=(4, 12)):
    m = {**MODEL, "num_hidden_layers": 1, "first_k_dense_replace": 0, "n_routed_experts": experts,
         "num_experts_per_tok": k, "held_experts": list(held)}
    return m, A.config_of(m), R.seeded_weights(m, 3, STD)["layers"][0]["moe"]


def test_the_routers_weights_are_unnormalised_probabilities_and_the_choice_ignores_no_expert(tokens):
    m, cfg, w = _moe_case(tokens)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(64, 32)), jnp.float32)
    bias = jnp.full((16,), 100.0).at[0].set(-100.0)  # would turn every choice around, if it were read
    with HIGHEST:
        chosen, weight, scores = M._route(w["router"], bias, x, cfg)
        prob = jax.nn.softmax(x @ w["router"], axis=-1)
    np.testing.assert_allclose(scores, prob, atol=1e-6)
    np.testing.assert_allclose(scores.sum(-1), 1.0, atol=1e-5)
    top_p, top_i = jax.lax.top_k(prob, 6)
    np.testing.assert_array_equal(chosen, top_i)  # greedy top-6 of the probabilities alone
    np.testing.assert_allclose(weight, top_p, atol=1e-6)
    assert np.all(np.asarray(weight.sum(-1)) < 0.999), "the six weights are not divided by their sum"
    assert set(np.unique(np.asarray(chosen))) == set(range(16)), "every expert is chosen by some token"
    # the other architecture's rule through the same function: sigmoid, the bias in the choice, normalised
    lfm2 = M.Lfm2MoeConfig(num_experts=16, num_experts_per_tok=6)
    _, weight2, scores2 = M._route(w["router"], jnp.zeros(16), x, lfm2)
    np.testing.assert_allclose(weight2.sum(-1), 1.0, atol=1e-4)
    assert float(scores2.sum(-1).max()) > 1.5


def test_the_balance_terms_gradient_reaches_the_router_and_nothing_else_of_the_expert_layer(tokens):
    m, cfg, w = _moe_case(tokens)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(64, 32)), jnp.float32)

    def balance(p, xs):
        return M._moe_ffn(p, None, xs, cfg, jnp.float32, sequences=2)[2].balance

    with HIGHEST:
        value, (dp, dx) = jax.value_and_grad(balance, argnums=(0, 1))(w, x)
        prob = jax.nn.softmax(x @ w["router"], axis=-1)
    chosen = jax.lax.top_k(prob, 6)[1].reshape(2, -1)
    by_hand = np.mean([np.sum(np.bincount(np.asarray(c), minlength=16) * 16 / (6 * 32) * np.asarray(pr).mean(0))
                       for c, pr in zip(chosen, prob.reshape(2, 32, 16))])
    assert float(value) == pytest.approx(by_hand, rel=1e-5) and 0.95 < float(value) < 1.6  # 1 where routing is even
    assert float(jnp.abs(dp["router"]).max()) > 1e-4
    assert float(jnp.abs(dx).max()) > 0, "through the router's input it reaches the layers below, as published"
    for path, g in jax.tree_util.tree_flatten_with_path({k: v for k, v in dp.items() if k != "router"})[0]:
        assert not np.asarray(g).any(), jax.tree_util.keystr(path)
    # a count carries no gradient: the term is linear in the probabilities' means
    stats_twice = M._balance_term(2.0 * prob, jax.lax.top_k(prob, 6)[1], 2, cfg)
    assert float(stats_twice) == pytest.approx(2.0 * float(value), rel=1e-5)


def test_the_grouped_products_tiles_follow_the_shape():
    """1536 = 3 x 512 keeps LFM2's tiles; 1408 = 11 x 128 is one tile, whole, as
    contraction (the down product, the backward's) and as columns; the row tile
    divides the buffer; a width far over four tiles stays tiled."""
    assert M._gmm_tiling(22528, 2048, 1536) == M._GMM_TILING == (512, 512, 512)
    assert M._gmm_tiling(33792, 2048, 1408) == (512, 512, 1408)
    assert M._gmm_tiling(33792, 1408, 2048) == (512, 1408, 512)
    assert M._gmm_tiling(98304, 2048, 1408)[0] == 512 and M._gmm_tiling(192, 32, 24)[0] == 64
    assert M._gmm_tiling(512, 2048, 10944) == (512, 512, 512)
    cfg = M.Lfm2MoeConfig(num_experts_per_tok=6)  # a mean share of 12,288 rows: 1.25 and 2.75 of them, in tiles of 512
    assert M._row_buffer_heights(cfg, 16384) == (15360, 33792, 98304)


# -- refusals, arithmetic, the species ------------------------------------------------------------------


def _pool(n=3):
    rng = np.random.default_rng(8)
    return [deepseek_v2_genome().default()] + [deepseek_v2_genome().sample(rng) for _ in range(n - 1)]


def _published():
    """(the configuration file, the family's module) of the benchmark's cell."""
    return F.config_file("deepseek_v2_lite_ep8"), F.family_module(A.family)


def test_the_published_cut_is_one_individual_wide_by_arithmetic():
    config, family = _published()
    params = family.model_params(config, 1, False)
    params.pop("seed")
    x = np.zeros((config["n_sequences"], config["data"]["seq_len"]), np.int32)
    cfg = M._normalize_config(x, params)[0]
    need = M.training_bytes(cfg)
    assert need["params"] == 635_466_752 and need["state"] == 16 * need["params"]  # 635.5 M, 10.17 GB
    assert round(need["state"] / 1e9, 2) == 10.17
    assert 16e9 / 2 < need["total"] < 16e9, "one individual fits a 16 GB chip, two do not"
    shapes = M.param_shapes(cfg)
    count = lambda tree: sum(math.prod(s) for s in jax.tree_util.tree_leaves(tree, is_leaf=lambda s: isinstance(s, tuple)))
    routed = shapes["layers"][1]
    assert count(routed["latent"]) == 13_763_072 and count(routed["moe"]["shared"]) == 17_301_504
    assert count({k: routed["moe"][k] for k in ("w1", "w3", "w2")}) == 69_206_016
    assert count(shapes["layers"][0]) == 81_007_104 and shapes["embed"] == shapes["head"] == (12800, 2048)
    assert cfg.gene_names[-1] == "aux_alpha" and cfg.tokens_per_step == 16384 and len(cfg.moe_layers) == 5
    # every width as published, and the cut stated
    for key, value in dict(hidden_size=2048, num_attention_heads=16, qk_nope_head_dim=128, qk_rope_head_dim=64,
                           v_head_dim=128, kv_lora_rank=512, intermediate_size=10944, moe_intermediate_size=1408,
                           n_routed_experts=64, num_experts_per_tok=6, n_shared_experts=2, first_k_dense_replace=1,
                           q_lora_rank=None).items():
        assert config[key] == value, key
    assert config["rope_scaling"] == YARN and set(config["reduced"]) == {
        "num_hidden_layers", "num_experts_held", "vocab_size", "train_steps", "n_sequences"}
    assert config["published"]["num_hidden_layers"] == 27 and config["published"]["vocab_size"] == 102400
    pool = family.make_pool(4, [20260928], -3.5)
    assert pool[0] == deepseek_v2_genome().default() and all(r["log10_lr"] <= -3.5 and "aux_alpha" in r for r in pool)


# -- telemetry ---------------------------------------------------------------------------------------


def test_fitness_is_the_same_with_telemetry_on_and_the_fetch_span_carries_the_balance_term(tokens):
    x, y = tokens
    kw = A.model_kwargs(seed=3)
    pool = _pool()
    base = M.Lfm2MoeModel.cross_validate_population(x, y, pool, **kw)
    assert np.all(base < 0) and len(set(base.tolist())) == len(pool)
    np.testing.assert_array_equal(M.Lfm2MoeModel.cross_validate_population(x, y, pool[::-1], **kw), base[::-1])
    with F.traced() as records:
        traced = M.Lfm2MoeModel.cross_validate_population(x, y, pool, **kw)
    np.testing.assert_array_equal(traced, base)
    fetched = F.span_attrs(records, "fetch")
    assert len(fetched) == len(pool) and all(0.95 < a["aux_loss"] < 2.0 for a in fetched)  # 1 where routing is even
    assert all(a["dropped"] == 0 and a["wide_buffer"] == 0 and np.shape(a["expert_rows"]) == (2, 2) for a in fetched)
    assert get_registry().counter("aux_loss_total").value == pytest.approx(sum(a["aux_loss"] for a in fetched))
    trained = [a for a in F.span_attrs(records) if a.get("steps")]
    assert [a["attention_kernel_layer_steps"] for a in trained] == [0] * len(pool)  # the CPU takes XLA's core


def test_a_train_span_counts_the_latent_layers_that_ran_the_fused_core(kernel_on_the_cpu):
    tok = np.random.default_rng(9).integers(0, 64, size=(6, 129)).astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    m = {**MODEL, "hidden_size": 64, "num_attention_heads": 1, "num_hidden_layers": 2, "qk_nope_head_dim": 128,
         "qk_rope_head_dim": 64, "v_head_dim": 128}
    programs = M.Lfm2MoeModel.compiled_programs(x, **A.model_kwargs(m, compute_dtype="bfloat16"))
    assert programs.attention_kernel_layers == 2  # a latent layer counts as an attention layer
    with F.traced() as records:
        loss = F.score_one(programs, x, y, GENES)
    assert 0 < loss < np.log(64) + 0.5
    trained = F.span_attrs(records, steps=3)
    assert [a["attention_kernel_layer_steps"] for a in trained] == [6]  # 2 latent layers x 3 steps
    assert get_registry().counter("attention_kernel_layer_steps_total", mask="causal").value == 6


# -- scopes ------------------------------------------------------------------------------------------


def test_the_lowered_train_step_carries_every_new_scope(tokens):
    programs = M.Lfm2MoeModel.compiled_programs(tokens[0], **A.model_kwargs(layer_ids=(0, 1, 2)))
    state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    text = programs.train_step.lower(state, *tokens, np.zeros((3, 2), np.int32), np.zeros(5, np.float32),
                                     np.int32(0)).as_text(debug_info=True)
    for scope in ("embed", "layer0", "layer1", "layer2", "latent_attention/down_proj", "latent_attention/up_proj",
                  "latent_attention/rope", "latent_attention/core", "latent_attention/out_proj", "dense_ffn",
                  "moe/router", "aux_loss", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared", "head", "loss",
                  "optimizer"):
        assert scope in text, scope
    assert "bias_update" not in text, "the aux_loss rule has no bias to step"
    names = set(re.findall(r'loc\("([^"]+)"', text))
    placed = {scope_rules.classify(n)[0] for n in names}
    assert {"latent_core", "latent_proj", "shared_expert", "expert_mm", "moe_route", "dense_ffn", "head_loss",
            "optimizer"} <= placed
    assert all(scope_rules.classify(n)[0] == "moe_route" for n in names if "/aux_loss/" in n)
    # the other architecture's program carries none of them, and keeps its own
    lfm2 = M.Lfm2MoeModel.compiled_programs(tokens[0], hidden_size=32, layer_types=("conv", "full_attention"),
                                            num_dense_layers=1, intermediate_size=48, moe_intermediate_size=24,
                                            num_experts=8, num_experts_per_tok=2, held_experts=(2, 4),
                                            num_attention_heads=4, num_key_value_heads=2, vocab_size=64, train_steps=3,
                                            batch_sequences=2, eval_sequences=2, attn_block=8, compute_dtype="float32")
    state = jax.eval_shape(lfm2.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    other = lfm2.train_step.lower(state, *tokens, np.zeros((3, 2), np.int32), np.zeros(5, np.float32),
                                  np.int32(0)).as_text(debug_info=True)
    assert "bias_update" in other and "aux_loss" not in state
    assert not [s for s in ("latent_attention", "aux_loss", "moe/shared") if s in other]


# -- the benchmark's counts and readers -----------------------------------------------------------------


def test_executed_flops_at_the_published_widths_by_hand():
    config, family = _published()
    m = family.model_block(config)
    assert flops.core_pair_elements(4096) == 10 * 1024 * 1024 and flops.core_pair_elements(512) == 512 * 512
    forward = flops.core_flops(m, 1, 4096, 1, 0)
    assert forward == 16 * 10 * 1024 * 1024 * 2 * (192 + 128)  # 16 heads, 10 block pairs, two products
    assert flops.core_flops(m, 1, 4096, 0, 1) == 16 * 10 * 1024 * 1024 * 2 * (3 * 192 + 2 * 128)
    per = flops.forward_flops_per_token(m, 4096)
    assert per["attention_core"] == 6 * forward / 4096 and per["head"] == 2 * 2048 * 12800
    latent = 2 * (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    routed = 2 * 2048 * 64 + 6 * 2048 * 2816
    assert per["linear"] == 6 * latent + 6 * 2048 * 10944 + 5 * routed
    rows_a_token = 5 * 6 / 8
    total = sum(per.values()) + rows_a_token * 3 * 2 * 2048 * 1408
    assert total == pytest.approx(0.7486e9, rel=1e-3)  # the issue's 0.749 GFLOP a token forward
    step = flops.train_flops(m, 16384, 16384 * rows_a_token, 4096)
    assert step == pytest.approx(49.74e12, rel=1e-3)
    # the core is bound by compute, six times over
    work, moved = flops.core_flops(m, 4, 4096, 2, 1), flops.core_bytes(m, 4, 4096, 2, 1)
    assert moved == 4 * 16 * 4096 * (2 * (2 * 640 + 4) + 2 * 1280 + 4) and work / 197e12 > 6 * moved / 819e9
    # the grouped products at 12,288 rows a layer-step: bound by compute too
    rows = 12288.0 * 40
    assert flops.expert_mm_flops(m, rows, 4) / 197e12 > flops.expert_mm_bytes(m, rows, 4, 40) / 819e9


@pytest.fixture()
def layer_metric():
    """A reader of ``benchmark/layer_metrics/`` by name, loaded as ``run.py`` loads it."""
    with F.as_run_py_loads(A.family) as load:
        yield lambda name: load(f"layer_metrics/{name}")


_span = F.span


def test_the_balance_reader_averages_the_windows_fetch_spans_and_a_program_without_the_attribute_reads_nothing(
        layer_metric):
    reader = layer_metric("dsv2_aux_loss_mean")
    window = {"window": (10.0, 20.0)}
    records = [_span("fetch", 5.0, {"individual": 0, "aux_loss": 9.0}),  # set-up's warm-up call
               _span("fetch", 11.0, {"individual": 0, "aux_loss": 1.0}), _span("fetch", 12.0, {"individual": 1, "aux_loss": 1.5}),
               _span("fetch", 13.0, {"other": 1, "aux_loss": 7.0})]
    assert reader.read({**window, "records": records}) == 1.25
    assert reader.read({**window, "records": [_span("fetch", 11.0, {"individual": 0, "expert_rows": [[1]]})]}) is None
    assert reader.read({**window, "records": records[:1]}) is None


def test_the_core_roofline_reader_divides_the_kernels_flops_by_the_kernels_own_time(layer_metric):
    """Two traced individuals of the published cell: 2 x 8 steps x 4 sequences x 6 layers of the core; the time is
    that of the instructions that carry the kernels' name, not the class's transposes and casts."""
    reader = layer_metric("dsv2_latent_core_roofline_share")
    config, family = _published()
    m = family.model_block(config)
    work = 6 * flops.core_flops(m, 2 * 8 * 4, 4096, 2, 1)
    least = work / 197e12
    table = {"individuals": 2, "programs": {
        "jit_lm_train_step(1)": {"ops": {"splash_mqa_fwd_residuals.1": ["latent_core", 0.4 * least / 0.5],
                                         "splash_mqa_dkv_no_residuals.1": ["latent_core", 0.6 * least / 0.5],
                                         "fusion.7": ["latent_core", 5.0], "fusion.9": ["latent_proj", 3.0]}},
        "jit_lm_eval(2)": {"ops": {"splash_mqa_fwd_no_residuals.1": ["latent_core", 9.0]}}}}
    run = {"scope_table": table, "config": config, "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert reader.read(run) == pytest.approx(50.0)
    table["programs"]["jit_lm_train_step(1)"]["ops"] = {"fusion.7": ["latent_core", least / 0.25]}  # XLA's core
    assert reader.read(run) == pytest.approx(25.0)
    assert reader.read({**run, "scope_table": None}) is None
    parent = {"individuals": 2, "programs": {"jit_lm_train_step(1)": {"ops": {"fusion.1": ["rest", 1.0]}}}}
    assert reader.read({**run, "scope_table": parent}) is None  # a program without the scope reports nothing
