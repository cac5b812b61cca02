"""Keye-VL-2.0's language model in the routed family's module (``models/lfm2_moe.py``, ``layer_types`` of
``sparse_attention``): what no other architecture has.  Attention whose keys a learned indexer chooses -- the
selection as a threshold found by bisection, the mask that is data against the reference's 0/1 array entry by entry,
the fenced gradient of the indexer's loss, the thresholds kept under rematerialisation -- and rope by sections over
position streams.  CPU, float32, seeded, at sizes with several groups of query blocks and queries on both sides of
``topk`` (96 positions, 16 keys kept, blocks of 8).  What it shares with the other architectures (logits, loss and
every gradient per layer kind, two train steps, the shares of the expert layer, refusals, scope rules, the cell's
readers) is in ``test_routed_family*.py`` under ``keye_vl2`` ids.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu import deepseek_v2_genome
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry.registry import get_registry
from routed_family import HIGHEST, sparse_kernels_on_the_cpu  # noqa: F401  (the fixture)

A = F.ARCHS["keye_vl2"]
R, flops, scope_rules = A.R, A.flops, A.scope_rules
#: One layer, 16 of 96 keys kept, 4 of 8 experts held.
LONG = {**A.model, "num_hidden_layers": 1, "topk": 16, "held_experts": [1, 5]}
IDENTITY = lambda a: a


@pytest.fixture(scope="module")
def long_tokens():
    tok = np.random.default_rng(4).integers(0, 64, size=(6, 97)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


@pytest.fixture(scope="module")
def long_cfg(long_tokens):
    return A.config_of(LONG, tokens=long_tokens)


def _expected_pairs(length, top):
    return top * (top + 1) // 2 + (length - top) * top


# -- the selection ---------------------------------------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 5, 16, 64, 97])
def test_the_kth_largest_by_bisection_is_the_sorted_rows_kth(k):
    """Negative, zero of both signs, equal and infinite scores among them: the bit pattern's order is the floats'."""
    x = np.random.default_rng(k).normal(size=(7, 97)).astype(np.float32)
    x[0, :20], x[1, 3], x[1, 4], x[2, 5:9] = -np.inf, 0.0, -0.0, 1.5
    x[3] = np.abs(x[3])
    x[4] = -np.abs(x[4])
    got = np.asarray(jax.jit(lambda a: M._kth_largest(a, k))(x))
    want = np.sort(x, axis=-1)[:, -k]
    assert np.array_equal(got, want)


def test_the_table_of_the_core_is_groups_of_blocks_each_against_the_keys_up_to_its_last_query():
    assert M._sparse_blocks(96, 8) == ((0, 32), (32, 64), (64, 96)) and M._SPARSE_GROUP == 4
    assert M._sparse_blocks(16384, 512) == tuple((first, first + 2048) for first in range(0, 16384, 2048))
    assert M._sparse_blocks(24, 8) == ((0, 24),) and M._sparse_blocks(8, 32) == ((0, 8),)
    assert M._sparse_visits(96, 8) == {"pairs": 12, "elements": 32 * (32 + 64 + 96)}
    assert M._sparse_visits(16384, 512) == {"pairs": 32, "elements": 2048 * sum(range(2048, 16385, 2048))}
    with pytest.raises(ValueError, match="multiple of attn_block"):
        M._sparse_blocks(100, 8)


def test_the_mask_that_is_data_is_the_references_array_entry_by_entry_and_counts_what_the_arithmetic_says(long_tokens, long_cfg):
    """Which keys each query keeps, as the programs choose them (operands, bisection, comparison, block by block in
    groups) against the reference's explicit 0/1 array (``lax.top_k``'s k-th value): every entry.  A query with no
    more than ``topk`` keys keeps them all; every other keeps exactly ``topk``; none keeps a key ahead of it."""
    x = long_tokens[0][:2]
    w = A.seeded_weights(LONG, 3)
    with HIGHEST:
        got = np.asarray(jax.jit(lambda p: M.selected_keys(long_cfg, p, jnp.zeros((1, 8)), x))(w))
        want = np.stack([R.selections(LONG, w, tokens) for tokens in x], axis=1)
    assert got.shape == want.shape == (1, 2, 96, 96) and got.dtype == bool
    assert np.array_equal(got, want), f"{(got != want).sum()} choices differ"
    kept = got[0, 0].sum(axis=1)
    assert np.array_equal(kept[:16], np.arange(1, 17)) and (kept[16:] == 16).all()
    assert not np.triu(got[0, 0], 1).any()
    assert got[0].sum() == 2 * _expected_pairs(96, 16) == 2 * flops.chosen_elements(LONG, 96)
    assert not np.array_equal(got[0, 0], got[0, 1]), "the choice follows the tokens"
    assert (np.tril(np.ones((96, 96), bool)) & ~got[0, 0])[16:].any(), "a query past topk leaves keys out"


def _layer_loss(cfg, w, embedded, probe, nll_weight=1.0, indexer_weight=1.0):
    """A loss through one layer that reads its output (``nll_weight``) and its indexer's own term (``indexer_weight``)."""
    out, (_, stats) = M._layer(cfg, 0, jnp.float32, w, None, embedded)
    return nll_weight * jnp.sum(out * probe) + indexer_weight * stats.indexer_loss, (out, stats)


def test_the_layer_its_gradients_its_loss_and_its_count_are_the_references_over_several_groups(long_tokens, long_cfg):
    x = long_tokens[0][:2]
    w_all = A.seeded_weights(LONG, 5)
    w, embedded = w_all["layers"][0], jnp.asarray(w_all["embed"][x])
    probe = jnp.asarray(np.random.default_rng(1).normal(size=embedded.shape), jnp.float32)

    def reference(w):
        outs = [R.layer(LONG, 0, IDENTITY, w, e) for e in embedded]
        out, loss = jnp.stack([o[0] for o in outs]), sum(o[3][0] for o in outs) / len(outs)
        return jnp.sum(out * probe) + loss, (out, loss, sum(o[3][1] for o in outs))

    with HIGHEST:
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(lambda w: _layer_loss(long_cfg, w, embedded, probe),
                                                              has_aux=True))(w)
        (_, (ref_out, ref_loss, ref_pairs)), ref_grads = jax.jit(jax.value_and_grad(reference, has_aux=True))(w)
    np.testing.assert_allclose(out, ref_out, atol=3e-5)
    np.testing.assert_allclose(stats.indexer_loss, ref_loss, rtol=2e-6)
    assert float(ref_loss) > 1e-3
    assert int(stats.selected) == int(ref_pairs) == 2 * _expected_pairs(96, 16)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.abs(r).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(g, r, atol=2e-5 * max(float(jnp.abs(r).max()), 1.0), rtol=1e-4, err_msg=jax.tree_util.keystr(path))


#: One layer at the smallest shape the fused kernels take: one super-tile of the bits (4,096 positions, blocks of 512),
#: 2 key-value heads of 2 query heads of 128 columns, 256 keys kept.
KERNEL = {**A.model, "num_hidden_layers": 1, "held_experts": [1, 5], "head_dim": 128, "mrope_section": [16, 24, 24], "topk": 256}


def test_the_layer_through_the_fused_kernels_is_the_references(sparse_kernels_on_the_cpu):
    """The whole layer once with the masked core as the three kernels (interpreted), under ``forward``'s
    rematerialisation that keeps the choice, the output and the log-sum-exp: its output, the indexer's loss, the count
    of kept pairs and every gradient against the reference's written-out arrays."""
    tok = np.random.default_rng(11).integers(0, 64, size=(2, 4097)).astype(np.int32)
    cfg = A.config_of(KERNEL, tokens=(tok[:, :-1], tok[:, 1:]), attn_block=512, batch_sequences=1, eval_sequences=1)
    tok = tok[:1]
    programs = M._programs(cfg)
    assert programs.sparse_core_layers == (("kernel", 1),) and dict(programs.sparse_kernel_visits)["tiles"] == 20
    w_all = A.seeded_weights(KERNEL, 5)
    w, embedded = w_all["layers"][0], jnp.asarray(w_all["embed"][tok[:, :-1]])
    probe = jnp.asarray(np.random.default_rng(1).normal(size=embedded.shape), jnp.float32)

    def ours(w):
        policy = jax.checkpoint_policies.save_only_these_names(*M.SPARSE_KEPT)
        fn = jax.checkpoint(lambda w: M._layer(cfg, 0, jnp.float32, w, None, embedded), policy=policy)
        out, (_, stats) = fn(w)
        return jnp.sum(out * probe) + stats.indexer_loss, (out, stats)

    def reference(w):
        out, _, _, (loss, pairs) = R.layer(KERNEL, 0, IDENTITY, w, embedded[0])
        return jnp.sum(out * probe[0]) + loss, (out[None], loss, pairs)

    def every(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from every(sub)

    differentiated = list(every(jax.make_jaxpr(jax.grad(lambda w: ours(w)[0]))(w).jaxpr))
    assert {e.params["name"] for e in differentiated if e.primitive.name == "name"} == set(M.SPARSE_KEPT)
    calls = [str(e.params["name"]) for e in differentiated if e.primitive.name == "pallas_call"]
    assert sum("sparse_core_fwd" in c for c in calls) == 1, "the forward kernel runs once: out and the log-sum-exp are kept"
    assert sum("sparse_core_bwd" in c for c in calls) == 1 and sum("sparse_core_share" in c for c in calls) == 2 * 2
    with HIGHEST:
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(ours, has_aux=True))(w)
        (_, (ref_out, ref_loss, ref_pairs)), ref_grads = jax.jit(jax.value_and_grad(reference, has_aux=True))(w)
    np.testing.assert_allclose(out, ref_out, atol=1e-4)
    np.testing.assert_allclose(stats.indexer_loss, ref_loss, rtol=2e-5)
    assert float(ref_loss) > 1e-3 and int(stats.selected) == int(ref_pairs) >= _expected_pairs(4096, 256)  # ties at the threshold are kept
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.abs(r).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(g, r, atol=1e-4 * max(float(jnp.abs(r).max()), 1.0), rtol=1e-3, err_msg=jax.tree_util.keystr(path))


def test_the_gradient_is_fenced_the_indexer_learns_from_its_loss_alone_and_nothing_else_learns_from_it(long_tokens, long_cfg):
    x = long_tokens[0][:2]
    w_all = A.seeded_weights(LONG, 6)
    w, embedded = w_all["layers"][0], jnp.asarray(w_all["embed"][x])
    probe = jnp.asarray(np.random.default_rng(2).normal(size=embedded.shape), jnp.float32)
    grad = jax.jit(lambda w, e, a, b: jax.grad(lambda w, e: _layer_loss(long_cfg, w, e, probe, a, b)[0], argnums=(0, 1))(w, e))
    with HIGHEST:
        from_the_output, into_the_stream = grad(w, embedded, 1.0, 0.0)
        from_the_indexers_loss, into_the_stream_too = grad(w, embedded, 0.0, 1.0)
    assert all(not np.asarray(g).any() for g in jax.tree_util.tree_leaves(from_the_output["indexer"])), \
        "the next-token loss moves no matrix of the indexer: the mask is a choice"
    assert all(np.asarray(g).any() for g in jax.tree_util.tree_leaves(from_the_indexers_loss["indexer"]))
    others = {k: v for k, v in from_the_indexers_loss.items() if k != "indexer"}
    assert all(not np.asarray(g).any() for g in jax.tree_util.tree_leaves(others)), \
        "the indexer's loss moves nothing else: its target is a constant and its input detached"
    assert not np.asarray(into_the_stream_too).any() and np.asarray(into_the_stream).any()


def test_the_selection_is_not_run_again_under_rematerialisation(long_tokens, long_cfg):
    """The choice is kept for the backward pass (a bit a query and key): the bisection's 32 counting passes (a loop
    under ``select``) stand in the differentiated program once a group that selects, not once more in the
    rematerialised forward."""
    x, y = long_tokens[0][:2], long_tokens[1][:2]
    w = jax.tree_util.tree_map(jnp.asarray, A.seeded_weights(LONG, 7))

    def loss(w, remat):
        logits, _, stats = M.forward(long_cfg, w, jnp.zeros((1, 8)), x, remat=remat)
        return M.token_loss(logits, y).mean() + stats.indexer_loss

    def loops(jaxpr):
        return sum(1 for name, scope, _ in F.equations(jaxpr) if name in ("while", "scan") and scope.rstrip("/").endswith("select"))

    def in_the_rematerialised_forward():
        differentiated = jax.make_jaxpr(jax.grad(lambda w: loss(w, True)))(w).jaxpr
        again = [e for e in differentiated.eqns if e.primitive.name.startswith(("remat", "checkpoint"))]
        assert len(again) == 1 and loops(differentiated) > 0
        return sum(loops(sub) for sub in jax.core.jaxprs_in_params(again[0].params))

    selecting = sum(1 for first, last in M._sparse_blocks(96, 8) if last > 16)
    assert loops(jax.make_jaxpr(lambda w: loss(w, False))(w).jaxpr) == selecting == 3
    assert in_the_rematerialised_forward() == 0, "the rematerialised forward selects again"
    with pytest.MonkeyPatch.context() as mp:  # a policy that keeps nothing: the layer's forward runs again whole
        mp.setattr(jax.checkpoint_policies, "save_only_these_names", lambda *names: None)
        assert in_the_rematerialised_forward() >= selecting
    with HIGHEST:
        plain, kept = jax.jit(jax.grad(lambda w: loss(w, False)))(w), jax.jit(jax.grad(lambda w: loss(w, True)))(w)
    for a, b in zip(jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(kept)):
        np.testing.assert_allclose(a, b, atol=1e-6)


# -- rope by sections ------------------------------------------------------------------------------------------------------


def test_rope_by_sections_reads_each_pairs_own_stream_and_equal_streams_are_plain_rope():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(2, 24, 3, 16)), jnp.float32)
    streams = np.stack([np.arange(24), np.arange(24) // 3, np.arange(24) % 5]).astype(np.float32)  # unequal: an image's
    sections = (2, 3, 3)
    with HIGHEST:
        got = M._rope_whole_heads(x, 1e7, sections=sections, positions=streams)
        want = jnp.stack([R.rope(s, 1e7, streams, sections) for s in x])
        np.testing.assert_allclose(got, want, atol=2e-6)
        # pair c reads stream s(c): moving the third stream leaves pairs 0-4 (columns 0-4 and 8-12) where they were
        moved = M._rope_whole_heads(x, 1e7, sections=sections, positions=streams + np.array([[0], [0], [7]], np.float32))
        same = np.isclose(np.asarray(moved), np.asarray(got), atol=1e-6).all(axis=(0, 1, 2))
        assert same[[0, 1, 2, 3, 4, 8, 9, 10, 11, 12]].all() and not same[[5, 6, 7, 13, 14, 15]].any()
        text = np.broadcast_to(np.arange(24, dtype=np.float32), (3, 24))
        plain = M._rope_whole_heads(x, 1e7)
        np.testing.assert_array_equal(M._rope_whole_heads(x, 1e7, sections=sections, positions=text), plain)
        np.testing.assert_array_equal(M._rope_whole_heads(x, 1e7, sections=sections), plain)
        np.testing.assert_allclose(plain, jnp.stack([R.rope(s, 1e7, text) for s in x]), atol=2e-6)
        # the indexer's rope: every column, at the first stream's positions
        np.testing.assert_allclose(M._rope(x, 1e7, positions=streams), jnp.stack([R.rope(s, 1e7, streams[:1]) for s in x]), atol=2e-6)


# -- scopes, spans, the counter --------------------------------------------------------------------------------------------


def test_the_layer_has_its_scopes_and_the_rules_class_them_under_the_accepted_readers_names(long_tokens, long_cfg):
    w = jax.tree_util.tree_map(jnp.asarray, A.seeded_weights(LONG, 1))
    x, y = long_tokens[0][:2], long_tokens[1][:2]

    def loss(w):
        logits, _, stats = M.forward(long_cfg, w, jnp.zeros((1, 8)), x, remat=True)
        return M.token_loss(logits, y).mean() + stats.indexer_loss

    scopes = F.scopes(jax.grad(loss), w)
    for part in ("proj", "rope", "indexer_proj", "indexer_scores", "select", "core", "indexer_loss"):
        assert any(f"layer0/sparse_attention/{part}" in s.replace("checkpoint/", "").replace("rematted_computation/", "")
                   for s in scopes), part
    classify = scope_rules.classify
    assert classify("jit(lm_train_step)/jvp(layer2)/sparse_attention/checkpoint/core/sngqk,sknd->sqngd/dot_general") == ("full_core", "core")
    assert classify("jit(lm_train_step)/transpose(jvp(layer2))/sparse_attention/rematted_computation/core/mul") == ("full_core", "core")
    assert classify("jit(lm_train_step)/jvp(layer1)/sparse_attention/while/body/checkpoint/indexer_scores/dot_general") == ("window_core", "indexer_scores")
    assert classify("jit(lm_train_step)/jvp(layer1)/sparse_attention/indexer_scores/while/body/reduce_sum") == ("window_core", "indexer_scores")
    assert classify("jit(lm_eval)/layer0/sparse_attention/select/while/body/reduce_sum") == ("window_core", "select")
    assert classify("jit(lm_train_step)/transpose(jvp(layer3))/sparse_attention/checkpoint/indexer_loss/log_softmax") == ("window_core", "indexer_loss")
    assert classify("layer3/sparse_attention/indexer_proj/dot_general") == ("attention_proj", "indexer_proj")
    assert classify("layer3/sparse_attention/rope/mul") == ("attention_proj", "rope")
    assert classify("jit(lm_train_step)/jvp(layer1)/aux_loss/reduce_sum") == ("moe_route", "aux_loss")
    assert classify("layer1/cond/branch_1_fun/moe/experts/gmm") == ("expert_mm", "experts")
    assert classify("optimizer/sqrt") == ("optimizer", "optimizer") and classify("") == ("unattributed", "")
    placed = {classify(s)[0] for s in scopes}
    assert placed <= set(scope_rules.CLASSES) and placed >= {"window_core", "full_core", "attention_proj", "expert_mm", "moe_route", "head_loss"}


def test_the_spans_and_the_counter_say_what_the_sparse_layers_did(long_tokens):
    x, y = long_tokens
    kw = A.model_kwargs({**LONG, "num_hidden_layers": 2, "train_steps": 2}, cache_dir=False)
    with F.traced() as records:
        fitness = M.Lfm2MoeModel.cross_validate_population(x, y, [deepseek_v2_genome().default()], **kw)
    assert np.isfinite(fitness).all()
    (train,), (fetch,) = F.span_attrs(records, steps=2), F.span_attrs(records, "fetch")
    assert train["sparse_attention_layer_steps"] == 4 and train["sparse_topk"] == 16 and train["indexer_heads"] == 8
    assert train["sparse_core_kernel_layer_steps"] == 0 and not any(k.startswith("sparse_kernel_") for k in train), \
        "XLA's query blocks: the CPU's path, and every shape's that the kernels' rule refuses"
    assert (train["sparse_core_pairs"], train["sparse_core_elements"]) == (12, 32 * (32 + 64 + 96))
    assert fetch["selected_pairs"] == [2 * 2 * _expected_pairs(96, 16)] * 2, "a layer, over 2 steps of 2 sequences"
    assert 0.0 < fetch["indexer_loss_mean"] < 5.0 and fetch["dropped"] == 0 and fetch["aux_loss"] > 0.5
    counter = get_registry().counter
    assert counter("sparse_attention_layer_steps_total", program="blockwise").value == 4
    assert counter("attention_kernel_layer_steps_total", mask="causal").value == 0
    programs = M.Lfm2MoeModel.compiled_programs(x, **kw)
    assert programs.sparse_core_layers == (("blockwise", 2),) and programs.attention_kernel_layers == 0
    assert dict(programs.sparse_core_visits) == {"pairs": 12, "elements": 6144}
    state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    assert state["selected_pairs"].shape == (2,) and state["indexer_loss"].shape == ()


def test_the_spans_and_the_counter_say_where_the_fused_kernels_ran(sparse_kernels_on_the_cpu):
    """An individual through the kernels (interpreted): the ``train`` span counts its layers x steps under
    ``sparse_core_kernel_layer_steps`` (what the accepted ``mel_full_kernel_layer_steps`` reads), carries what the kernels
    visit off their own table beside what XLA's blocks (the selection, the loss pass) visit off theirs, and the counter
    names the program."""
    tok = np.random.default_rng(12).integers(0, 64, size=(2, 4097)).astype(np.int32)
    kw = A.model_kwargs({**KERNEL, "train_steps": 1}, cache_dir=False, attn_block=512, batch_sequences=1, eval_sequences=1)
    with F.traced() as records:
        fitness = M.Lfm2MoeModel.cross_validate_population(tok[:, :-1], tok[:, 1:], [deepseek_v2_genome().default()], **kw)
    assert np.isfinite(fitness).all()
    (train,), (fetch,) = F.span_attrs(records, steps=1), F.span_attrs(records, "fetch")
    assert train["sparse_attention_layer_steps"] == train["sparse_core_kernel_layer_steps"] == 1
    assert (train["sparse_kernel_tiles"], train["sparse_kernel_elements"], train["sparse_kernel_elements_bwd"]) == (20, 20 * 512 * 1024, 20 * 512 * 1024)
    assert (train["sparse_core_pairs"], train["sparse_core_elements"]) == (8, 2048 * (2048 + 4096))
    assert fetch["selected_pairs"][0] >= _expected_pairs(4096, 256) and 0.0 < fetch["indexer_loss_mean"] < 5.0
    assert get_registry().counter("sparse_attention_layer_steps_total", program="kernel").value == 1


# -- the benchmark's family: configuration file, counts, readers -----------------------------------------------------------


def test_the_configuration_file_holds_the_catalogs_numbers_and_the_cut_is_the_bytes_it_says():
    config, family, cfg = F.published_cfg("keye_vl2", "keye_vl2_30b_a3b_ep8")
    published = dict(hidden_size=2048, head_dim=128, num_attention_heads=32, num_key_value_heads=4, intermediate_size=6144,
                     moe_intermediate_size=768, num_experts=128, num_local_experts=128, num_experts_per_tok=8,
                     max_position_embeddings=262144, max_window_layers=48, rms_norm_eps=1e-6, rope_theta=10000000,
                     decoder_sparse_step=1, mlp_only_layers=[], model_type="KeyeVL2", norm_topk_prob=True,
                     sliding_window=None, use_sliding_window=False, tie_word_embeddings=False, attention_bias=False,
                     hidden_act="silu")
    for key, value in published.items():
        assert config[key] == value, key
    assert config["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                                   "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert config["rope_scaling"] == {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"}
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "vision_tower", "train_steps",
                                      "n_sequences"}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] == 151936 and config["num_hidden_layers"] == 4
    assert {"indexer_input", "indexer_rope", "indexer_key_norm", "chunk_sizes", "indexer_loss", "precision", "qk_norm",
            "rope"} <= set(config["assumed"]) and "30.64 B" in config["published"]["parameters"]
    assert all("Other reading" in config["assumed"][k] for k in ("indexer_input", "indexer_rope", "indexer_key_norm", "chunk_sizes"))
    m = family.model_block(config)
    assert (m["indexer_num_heads"], m["indexer_head_dim"], m["topk"], m["mrope_section"]) == (16, 64, 2048, [16, 24, 24])
    need = M.training_bytes(cfg)
    assert need["params"] == 465_390_592 and need["state"] == 7_446_249_472, "to the unit"
    assert cfg.layer_types == ("sparse_attention",) * 4 == tuple(cfg.layer_types) and cfg.sparse_layers == (0, 1, 2, 3)
    assert cfg.tokens_per_step == 16384 and cfg.batch_sequences == 1 and cfg.qk_norm and cfg.typed_attention
    assert cfg.n_held == 16 and cfg.balance_rule == "aux_loss" and cfg.scoring_func == "softmax" and cfg.num_dense_layers == 0
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.sparse_topk, cfg.mrope_section) == (16, 64, 2048, (16, 24, 24))
    shapes = M.param_shapes(cfg)
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(tree, is_leaf=M._is_shape))
    layer = shapes["layers"][0]
    assert layer["attn"] == {"q": (2048, 4096), "k": (2048, 512), "v": (2048, 512), "o": (4096, 2048), "q_norm": (128,),
                             "k_norm": (128,)} and count(layer["attn"]) == 18_874_624
    assert layer["indexer"] == {"q": (2048, 1024), "k": (2048, 64), "w": (2048, 16)} and count(layer["indexer"]) == 2_260_992
    assert layer["moe"]["router"] == (2048, 128) and layer["moe"]["w1"] == (16, 2048, 768)
    assert count(layer["moe"]) == 262_144 + 16 * 4_718_592 and count(layer) == 96_899_328
    assert count({k: v for k, v in shapes.items() if k != "layers"}) == 2 * 18_992 * 2048 + 2048 == 77_793_280
    # the whole model by the same count: the parameters the row states
    whole = 48 * (21_401_856 + 128 * 4_718_592) + 2 * 151_936 * 2048 + 2048
    assert 48 * 625_381_632 + 622_331_904 == whole and round(whole / 1e9, 2) == 30.64, whole
    # 87.5% of the queries choose; 2,048 of 9,216 causal keys on average over the others
    assert flops.chosen_elements(m, 16384) == 31_458_304 and flops.causal_elements(16384) == 16384 * 16385 // 2
    assert flops.block_elements(16384, 512) == M._sparse_visits(16384, 512)
    total = flops.train_flops(m, 16384, 16384 * 8 / 8 * 4, 16384, {"full_attention": M._sparse_visits(16384, 512)})
    assert 60e12 < total < 120e12, total


def test_the_cell_runs_the_accepted_mix_as_it_is_on_one_fixed_window_and_checks_on_the_seeds_inputs():
    config, mix = F.config_file("keye_vl2_30b_a3b_ep8"), F.traffic_mix()
    family, mellum = F.family_module("keye_vl2"), F.family_module("mellum")
    pool = family.make_pool(4, [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
    assert len(pool) == config["population"] == 4 and pool == mellum.make_pool(4, [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
    assert pool[0] == {"log10_lr": -3.5, "warmup_frac": 0.25, "weight_decay": 0.1, "beta2": 0.95, "aux_alpha": 0.001}
    small = {**config, "n_sequences": 6, "data": {**config["data"], "seq_len": 16}}
    a, b = (family.make_inputs(small, mix, seed) for seed in (3, 2147484001))
    assert a["pool"] == pool and np.array_equal(a["check_x"][:, 1:], a["check_y"][:, :-1])
    assert not np.array_equal(a["check_x"], b["check_x"])
    # one fixed pool for the window (seed-drawn the rate spread 0.52% and one seed of eight diverged:
    # ``assumed.window_inputs``): every seed times the same work, from the weights and tokens of ``window_seed``
    assert config["window_seed"] == 4900000303 and a["params"]["seed"] == config["window_seed"] % (2**31 - 1)
    assert np.array_equal(a["x"], b["x"]) and a["params"] == b["params"] and not np.array_equal(a["x"], a["check_x"])
    assert np.array_equal(a["x"], family.markov_tokens(small["data"], small["vocab_size"], 6, 16, config["window_seed"])[:, :-1])
    assert a["params"]["layer_types"] == ("sparse_attention",) * 4 and a["params"]["sparse_topk"] == 2048
    manifest = F.manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == "keye_vl2_30b_a3b_ep8.popeval")
    assert cell == {**cell, "config": "keye_vl2_30b_a3b_ep8", "traffic": "lmpopeval_fresh", "chips": 1}


def test_the_accepted_readers_read_the_masked_core_and_the_indexer_off_this_familys_spans_and_the_parent_reads_nothing():
    config = F.config_file("keye_vl2_30b_a3b_ep8")
    train = lambda t, **attrs: F.span("train", t, {"individual": 0, "steps": 8, "tokens": 8 * 16384, **attrs})
    sparse = dict(sparse_attention_layer_steps=32, sparse_topk=2048, indexer_heads=16, sparse_core_kernel_layer_steps=0,
                  sparse_core_pairs=32, sparse_core_elements=150_994_944)
    run = {"window": (10.0, 20.0), "config": config}
    records = [train(5.0, **sparse), train(11.0, **sparse), train(12.0, **sparse),
               F.span("train", 13.0, {"fold": 0, "sparse_core_kernel_layer_steps": 99})]  # no span of this family
    with F.as_run_py_loads(A.family) as load:
        full, window = load("layer_metrics/mel_full_kernel_layer_steps"), load("layer_metrics/mel_window_kernel_layer_steps")
        assert full.read({**run, "records": records}) == 0 == window.read({**run, "records": records})
        parent = [train(11.0, attention_kernel_layer_steps=0)]  # a program without the attribute
        assert full.read({**run, "records": parent}) is None and window.read({**run, "records": parent}) is None
        helper = sys.modules["mel_spans"]
        assert os.path.dirname(helper.__file__) == A.directory, "the readers' helper is this family's"
        assert helper.core_visits({**run, "records": records}, "full_attention") == {"pairs": 32, "elements": 150_994_944}
        assert helper.core_visits({**run, "records": parent}, "sliding_attention") is None
        empty = F.empty_run(config, "keye_vl2_30b_a3b_ep8.popeval")
        assert helper.core_roofline_share({**empty, "records": records}, "full_attention") is None  # no trace, no share
