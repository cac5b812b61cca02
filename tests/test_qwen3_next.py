"""What is Qwen3-Next-80B-A3B-Instruct's own among the routed family's tests (the fourth architecture through
``models/lfm2_moe.py``, against ``benchmark/families/qwen3_next/reference.py``, at small sizes on the CPU); what
every architecture is held to (logits, loss and gradients a Gated DeltaNet layer, a gated attention layer, one
period; two train steps; the shares with the mixer, the shared expert and its gate counted once; refusals; the
manifest's readers) is in ``test_routed_family*.py`` under ``qwen3_next-`` ids.

Here: partial rope; the published norm's ``1 + w`` form; which leaves weight decay reaches; the fused core's blocks
by shape; the scopes, the spans and the labelled counter; the configuration file and its counts; and the readers of
the per-layer metrics.  The delta rule alone (chunks against the recurrence, the fused kernels, the state's scan) is
in ``test_qwen3_next_delta.py``.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu import deepseek_v2_genome
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry.registry import get_registry
from routed_family import HIGHEST, ROWS, STD

A = F.ARCHS["qwen3_next"]
R, flops, scope_rules = A.R, A.flops, A.scope_rules
CELL = "qwen3_next_80b_a3b_ep16.popeval"
MODEL, PAIR, GENES, PERIOD = A.model, A.step_model, A.genes, F.Q3N_PERIOD
NO_BIAS = jnp.zeros((8, 8), jnp.float32)  # the state's bias: zeros, never read under the ``aux_loss`` rule


@pytest.fixture(scope="module")
def tokens():
    return A.tokens


def _program_steps(programs, weights, x, y, rows, steps, genes=GENES):
    return F.program_steps(programs, weights, x, y, rows, steps, genes)


def test_no_weight_decay_reaches_a_log_dt_bias_or_a_norm_and_they_start_from_their_own_values(tokens):
    """Under a recipe that is all weight decay (no gradient step to speak of: a learning rate times a decay of 1),
    the leaves that are no matrix keep what the gradient alone gives them; and a run starts them at ln u, 1 and 1."""
    x, y = tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **A.model_kwargs(PAIR))
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


def test_the_kinds_of_layer_say_which_are_attention_over_keys():
    assert set(M.ATTENTION_KINDS) == {"full_attention", "sliding_attention", "latent_attention"}
    # a scanning mixer, a feed-forward alone, and attention whose mask is data (its core is not ``_causal_core``'s)
    assert set(M.LAYER_KINDS) == set(M.ATTENTION_KINDS) | {"conv", "linear_attention", "mamba2", "routed", "sparse_attention"}
    lfm2 = M.Lfm2MoeConfig()
    assert not lfm2.typed_attention and lfm2.rotary_dim == lfm2.head_dim == 64
    assert M.Lfm2MoeConfig(layer_types=("linear_attention", "full_attention"), layer_ids=(0, 1)).typed_attention


_scopes = F.scopes


def test_each_layer_type_has_its_own_scope_with_its_parts_inside(tokens):
    cfg = A.config_of()
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


@pytest.fixture(scope="module")
def two_traced_individuals(tokens):
    """Two individuals of two delta layers and a full one scored with telemetry on: (programs, the ``train``
    spans' attributes, the labelled counter's value, the fitnesses)."""
    x, y = tokens
    kw = A.model_kwargs({**MODEL, "num_hidden_layers": 3, "layer_types": PERIOD[1:]}, cache_dir=False)
    programs = M.Lfm2MoeModel.compiled_programs(x, **kw)
    with F.traced() as records:
        fitness = M.Lfm2MoeModel.cross_validate_population(x, y, [deepseek_v2_genome().default()] * 2, **kw)
    trained = F.span_attrs(records, steps=3)
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
    kw = A.model_kwargs({**MODEL, "num_hidden_layers": 3, "layer_types": PERIOD[1:]}, cache_dir=False)
    try:
        with F.traced() as records:
            by_kernels = M.Lfm2MoeModel.cross_validate_population(x, y, [deepseek_v2_genome().default()], **kw)
            engaged = M.Lfm2MoeModel.compiled_programs(x, **kw)
    finally:
        M._programs.cache_clear()
    attrs = F.span_attrs(records, steps=3)[0]
    assert engaged.linear_core_kernel_layers == 2 and engaged.linear_core_layers == (("chunked", 2),)
    assert (attrs["linear_core_kernel_layer_steps"], attrs["linear_core_layer_steps_chunked"], attrs["linear_core_chunk"],
            attrs["linear_core_chain_products"]) == (6, 6, 8, M.LINEAR_CORE_KERNEL_CHAIN_PRODUCTS)
    assert attrs["linear_core_inverse_products"] == engaged.linear_core_inverse_products == delta_kernel.inverse_products(8, 2) == 2
    assert get_registry().counter("linear_core_kernel_layer_steps_total").value == 6
    assert get_registry().counter("linear_core_layer_steps_total", program="chunked").value == 6
    np.testing.assert_allclose(by_kernels[0], fitness[0], rtol=1e-5)


# -- the benchmark's family: configuration file, counts, readers ----------------------------------------------------


def _config_file():
    return F.config_file("qwen3_next_80b_a3b_ep16")


@pytest.fixture()
def family_modules():
    """The family's files (``"family"``) and the readers (``"layer_metrics/<name>"``) as ``run.py`` loads them."""
    with F.as_run_py_loads(A.family) as load:
        yield load


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
    mix = F.traffic_mix()
    family = family_modules("family")
    small = {**config, "n_sequences": 4, "data": {**config["data"], "seq_len": 16}}
    a, b, again = (family.make_inputs(small, mix, seed) for seed in (3, 2147484001, 3))
    pool = a["pool"]
    assert pool == b["pool"] and len(pool) == config["population"] == 4
    assert pool[0] == {"log10_lr": -3.5, "warmup_frac": 0.25, "weight_decay": 0.1, "beta2": 0.95, "aux_alpha": 0.001}
    assert all(r["log10_lr"] <= mix["pool_log10_lr_max"] for r in pool) and "pool_log10_lr_max" not in config
    assert np.array_equal(a["x"], again["x"]) and a["x"].shape == (4, 16) and np.array_equal(a["x"][:, 1:], a["y"][:, :-1])
    assert int(a["x"].max()) < config["vocab_size"]


_span = F.span


def test_the_layer_step_readers_read_the_windows_train_spans_and_the_parent_reads_nothing(family_modules):
    chunked = family_modules("layer_metrics/q3n_delta_chunked_layer_steps")
    kernel = family_modules("layer_metrics/q3n_full_kernel_layer_steps")
    train = lambda t, **attrs: _span("train", t, {"individual": 0, "steps": 8, **attrs})
    window = {"window": (10.0, 20.0)}
    records = [train(5.0, linear_core_layer_steps_chunked=0, attention_kernel_layer_steps_causal=0),  # set-up
               train(11.0, linear_core_layer_steps_chunked=24, attention_kernel_layer_steps_causal=8),
               train(12.0, linear_core_layer_steps_chunked=24, attention_kernel_layer_steps_causal=8),
               _span("train", 13.0, {"fold": 0, "linear_core_layer_steps_chunked": 99})]  # no span of this family
    assert chunked.read({**window, "records": records}) == 24 and kernel.read({**window, "records": records}) == 8
    assert chunked.read({**window, "records": [train(11.0, attention_kernel_layer_steps=64)]}) is None  # the parent
    assert kernel.read({**window, "records": records[:1]}) is None
