"""What is Laguna-XS.2's own among the routed family's tests (the fifth architecture through
``models/lfm2_moe.py``, against ``benchmark/families/laguna/reference.py``, at small sizes on the CPU); what every
architecture is held to (logits, loss and every gradient under a router bias that changes the choice: a layer of
each head count alone, the dense layer, the cut whole, two periods; two train steps with the bias's step; the eight
shares of the scaled routed sum with the shared expert counted once; refusals that name the layer; the manifest's
readers) is in ``test_routed_family*.py`` under ``laguna-`` ids.

Here: the gate a head against the reference and against zero gates; rope on half a head under YaRN against a table
built from the definition; the window's mask object entry by entry at a window of 512, one key short and one key
long failing; both cores at both groupings (XLA's blocks; the kernel's table of visits at group 6 and 8; the kernel
interpreted with both groups and both masks in one program); the scopes, the spans, the counter; the configuration
file, the counts and the accepted readers that read the new cell; and that the four architectures that were there
build the trees and the bytes they built.
"""

from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu import lfm2_moe_genome
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry.registry import get_registry
from routed_family import HIGHEST, STD, kernel_on_the_cpu, small_kernel_blocks  # noqa: F401  (the fixtures)

A = F.ARCHS["laguna"]
R, flops, scope_rules = A.R, A.flops, A.scope_rules
CELL = "laguna_xs2_ep8.popeval"
CUT, ROPE = A.model, F.LAGUNA_ROPE
one_layer = F.laguna_one_layer
_rel = F.rel


@pytest.fixture(scope="module")
def tokens():
    return A.tokens


# -- the gate a head ---------------------------------------------------------------------------------------


def _attention_weights(m, index=0, seed=2, gate_std=0.5):
    return jax.tree_util.tree_map(jnp.asarray, R.seeded_weights(m, seed, STD, gate_std=gate_std)["layers"][index]["attn"])


@pytest.mark.parametrize("kind,heads", [("sliding_attention", 6), ("full_attention", 4)])
def test_the_gate_a_head_is_the_references_and_zero_gates_halve_the_output(kind, heads, tokens):
    m = one_layer(kind, heads)
    cfg = A.config_of(m)
    p = _attention_weights(m)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 24, 40)), jnp.float32)
    identity = lambda a: a
    operator = jax.jit(lambda p, x: M._attention(p, x, cfg, jnp.float32, kind))
    with HIGHEST:
        got = operator(p, x)
        want = jnp.stack([R.attention(p, xs, m, kind, heads, identity) for xs in x])
        ungated = jnp.stack([R.attention(p, xs, {**m, "head_gate": False}, kind, heads, identity) for xs in x])
        halved = operator({**p, "gate": jnp.zeros_like(p["gate"])}, x)
    assert p["gate"].shape == (40, heads)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(halved, 0.5 * ungated, atol=2e-6)
    assert float(jnp.abs(got - ungated).max()) > 1e-2 and float(jnp.abs(got - halved).max()) > 1e-2
    # one scalar a head: the same gate on every column of a head, another on the next head's
    with HIGHEST:
        ratio = np.asarray(jnp.stack([R.attention({**p, "o": jnp.eye(heads * 16, dtype=jnp.float32)}, xs, {**m, "hidden_size": heads * 16},
                                                  kind, heads, identity) for xs in x])
                           / jnp.stack([R.attention({**p, "o": jnp.eye(heads * 16, dtype=jnp.float32)}, xs,
                                                    {**m, "hidden_size": heads * 16, "head_gate": False}, kind, heads, identity)
                                        for xs in x])).reshape(2, 24, heads, 16)
    np.testing.assert_allclose(ratio, np.broadcast_to(ratio[..., :1], ratio.shape), rtol=1e-4)
    np.testing.assert_allclose(ratio[..., 0], jax.nn.sigmoid(x @ p["gate"]), rtol=1e-4)


# -- rope on half a head under YaRN ------------------------------------------------------------------------


def test_partial_yarn_rope_is_a_table_built_from_the_definition_and_columns_64_to_127_pass():
    r = ROPE["full_attention"]
    cfg = M.Lfm2MoeConfig(rope_parameters=F.rope_table(ROPE),
                          head_dim=128, num_attention_heads=48, layer_types=("full_attention", "sliding_attention"),
                          layer_ids=(0, 1), sliding_window=512)
    assert cfg.rotary_of("full_attention") == 64 and cfg.rotary_of("sliding_attention") == 128
    assert cfg.rotary_dim == 128, "the configuration's one share stays what it was: the layer type's block decides"
    (theta, scaling), (plain_theta, none) = cfg.rope_of("full_attention"), cfg.rope_of("sliding_attention")
    assert (theta, plain_theta, none) == (5e5, 1e4, None) and M.yarn_amplitude(scaling) == 1.4158883083359672 \
        == pytest.approx(0.1 * math.log(64) + 1)
    # the table by hand, over the 64 rotated columns: the correction dimensions of beta_fast 64 and beta_slow 1
    d = lambda beta: 64 * math.log(4096 / (2 * math.pi * beta)) / (2 * math.log(5e5))
    low, high = math.floor(d(64)), math.ceil(d(1))
    assert (low, high) == (5, 16)
    c = np.arange(32)
    plain = 5e5 ** (-2.0 * c / 64)
    g = 1.0 - np.clip((c - low) / (high - low), 0.0, 1.0)
    inv_freq = (1 - g) * plain / 64 + g * plain
    np.testing.assert_allclose(M.yarn_inv_freq(64, 5e5, scaling), inv_freq, rtol=1e-6)
    np.testing.assert_allclose(R.rope_frequencies(64, r)[0], inv_freq, rtol=1e-12)
    assert inv_freq[4] == plain[4] and inv_freq[17] == pytest.approx(plain[17] / 64)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 40, 2, 3, 128)), jnp.float32)
    with HIGHEST:
        got = M._rope_whole_heads(x, theta, scaling, cfg.rotary_of("full_attention"))
    angle = np.arange(40)[:, None] * inv_freq[None, :]
    cos, sin = ((1.4158883083359672 * f(angle))[None, :, None, None, :] for f in (np.cos, np.sin))
    xs = np.asarray(x, np.float64)
    want = np.concatenate([xs[..., :32] * cos - xs[..., 32:64] * sin, xs[..., 32:64] * cos + xs[..., :32] * sin,
                           xs[..., 64:]], axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[..., 64:], np.asarray(x)[..., 64:])  # untouched, to the bit
    ref = jnp.stack([R.rope(x[0, :, 0], r, 64)])
    np.testing.assert_allclose(ref[0], want[0, :, 0], atol=2e-5)
    whole = M._rope_whole_heads(x, theta, scaling, 128)  # the fault "rope on the whole head" is another function
    assert float(jnp.abs(whole - got)[..., 64:].max()) > 0.1
    sliding = M._rope_whole_heads(x, plain_theta, None, cfg.rotary_of("sliding_attention"))
    np.testing.assert_allclose(sliding[0, :, 0], R.rope(x[0, :, 0], ROPE["sliding_attention"], 128), atol=2e-5)


# -- the window's mask, and both cores at both groupings ----------------------------------------------------------


_masks_handed_to_the_kernel = F.masks_handed_to_the_kernel


@pytest.mark.parametrize("length", [512, 1024, 4096, 8192])
def test_the_windows_mask_object_at_512_is_the_references_array_entry_by_entry(length):
    """What the fused kernel is handed at 8 query heads a key-value head under the window and at 6 under the causal
    mask (the library's ``LocalMask`` / ``CausalMask``, evaluated on the host: no TPU) against ``reference.visible``'s
    0/1 array: every entry, in slices of rows; a window one key short or one key long is another array.  At 512
    positions the window is as long as the sequence and the core runs unbanded; past it the kernel is handed one
    chunk's rectangle with the segments that shut out the keys before position 0, and laid back through the chunk
    layout (``F.rows_the_kernel_lets_through``) that is the reference's array too, in the first chunk (the window
    reaches 512 keys before the sequence) as in the last; 511 and 513 are no whole lanes and run unbanded."""
    causal = _masks_handed_to_the_kernel(length, 6, None)
    short, long = _masks_handed_to_the_kernel(length, 8, 511), _masks_handed_to_the_kernel(length, 8, 513)
    chunk = M._kernel_chunk(length, 512, 8)
    assert bool(chunk) == (length > 512) and len(causal) == 6 and len(short) == len(long) == 8
    j = np.arange(length)[None, :]
    differ = {"short": 0, "long": 0}
    seen = lambda mask, rows: np.asarray(mask[rows, :]).astype(bool)
    for first in range(0, length, 1024):
        rows = slice(first, min(first + 1024, length))
        i = np.arange(length)[rows, None]
        want = np.asarray(R.visible(i, j, "sliding_attention", {"sliding_window": 512})) == 1
        for head in (0, 7):
            np.testing.assert_array_equal(F.rows_the_kernel_lets_through(length, 8, 512, head, rows) == 1, want)
        np.testing.assert_array_equal(seen(causal[0], rows), np.asarray(R.visible(i, j, "full_attention", {})) == 1)
        differ["short"] += np.count_nonzero(seen(short[0], rows) != want)
        differ["long"] += np.count_nonzero(seen(long[0], rows) != want)
    assert differ["short"] == max(length - 511, 0) and differ["long"] == max(length - 512, 0)  # one key a row that has it
    last = F.rows_the_kernel_lets_through(length, 8, 512, 0, slice(length - (chunk or 1), length))[-1]
    assert int(last.sum()) == min(512, length)


def _core_case(length, group, seed=0, sequences=2, kv_heads=2, head=16):
    rng = np.random.default_rng([seed, length, group])
    q = jnp.asarray(rng.normal(size=(sequences, length, kv_heads, group, head)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(sequences, length, kv_heads, head)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(sequences, length, kv_heads, head)), jnp.float32)
    return q, k, v


def _written_out_core(q, k, v, scale, kind, window):
    """Every query head against its key-value head's keys under ``R.visible``'s 0/1 array, head by head."""
    length, group = q.shape[1], q.shape[3]
    i = jnp.arange(length)
    mask = R.visible(i[:, None], i[None, :], kind, {"sliding_window": window})
    out = []
    for n in range(q.shape[2] * group):
        scores = jnp.einsum("sqd,skd->sqk", q[:, :, n // group, n % group], k[:, :, n // group]) * scale
        prob = jax.nn.softmax(jnp.where(mask == 1, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("sqk,skd->sqd", prob, v[:, :, n // group]))
    return jnp.stack(out, axis=2).reshape(q.shape)


@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("kind,window", [("sliding_attention", 16), ("full_attention", None)])
def test_the_blockwise_core_at_both_groupings_is_the_written_out_softmax(kind, window, group):
    q, k, v = _core_case(96, group)
    with HIGHEST:
        got = M._blockwise_core(q, k, v, 0.25, 32, window)
        want = _written_out_core(q, k, v, 0.25, kind, window or 0)
        regrouped = M._blockwise_core(q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2), 0.25, 32, window)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(regrouped - want).max()) > 1e-2, "query heads served by the wrong key-value head is another function"


@pytest.mark.parametrize("group", [6, 8])
def test_the_kernels_table_of_visits_is_one_for_every_head_of_either_group_and_flops_py_counts_the_same(group):
    """The kernel built at a group of 6 and of 8 query heads under both masks (``jax.ensure_compile_time_eval``: on
    the host, no TPU).  At 8,192 positions and 1,024 x 1,024 blocks 36 of 64 pairs under the causal mask, whatever
    the group, and ``flops.block_visits`` counts the same.  Under the window of 512 the core runs banded: the kernel
    is ONE chunk's, its table holds every (query block, key block) pair of the chunk's rectangle for all the group's
    heads together, and a head visits ``length x (chunk + window)`` elements -- below the 15 pairs of 1,024 x 1,024
    that the unbanded kernel cost at 512 and at 1,024 alike (``flops.block_visits``: the XLA fall-back's and the
    benchmark's arithmetic), now smaller at 512 than at 1,024, and the mask lets through well over half of it."""
    m = {"sliding_window": 512}
    with jax.ensure_compile_time_eval():
        kernel = M._splash_kernel(8192, group, None)
    table = np.asarray(kernel.fwd_mask_info.block_mask)
    assert table.shape[0] in (1, group) and all(np.count_nonzero(head) == 36 for head in table)
    assert np.count_nonzero(np.asarray(kernel.dkv_mask_info.block_mask)[0]) == 36
    assert M._kernel_visits(8192, None, 256, group) == flops.block_visits(m, "full_attention", 8192)
    chunk = M._kernel_chunk(8192, 512, group, 256)
    assert chunk and 512 % chunk == 0 and (group * chunk) % 1024 == 0  # whole query blocks of every chunk's rows
    with jax.ensure_compile_time_eval():
        kernel = M._splash_kernel(8192, group, 512, 256)
    for info in (kernel.fwd_mask_info, kernel.dkv_mask_info):
        table = np.asarray(info.block_mask)
        assert table.shape[0] == 1 and table[0].size == np.count_nonzero(table[0]) == group * chunk // 1024  # one key block a chunk
    visits, unbanded = M._kernel_visits(8192, 512, 256, group), flops.block_visits(m, "sliding_attention", 8192)
    assert visits["chunk"] == chunk and visits["elements"] == 8192 * (chunk + 512) == visits["elements_bwd"]
    assert unbanded["pairs"] == 15 and visits["elements"] < unbanded["elements"] == 15 * 2**20
    assert visits["elements"] < M._kernel_visits(8192, 1024, 256, group)["elements"] < unbanded["elements"]
    assert flops.visible_elements(m, "sliding_attention", 8192) == 4_063_488
    assert flops.visible_elements(m, "sliding_attention", 8192) / unbanded["elements"] == pytest.approx(0.258, abs=1e-3)
    if group == 8:  # the published windowed layers' group
        assert flops.visible_elements(m, "sliding_attention", 8192) / visits["elements"] > 0.6
    assert flops.visible_elements(m, "full_attention", 8192) == 8192 * 8193 // 2
    i = np.arange(1024)
    for kind in ("sliding_attention", "full_attention"):
        assert flops.visible_elements({"sliding_window": 100}, kind, 1024) == \
            int(np.asarray(R.visible(i[:, None], i[None, :], kind, {"sliding_window": 100})).sum())
    for kind in flops.KERNEL_BLOCKS:  # one set of blocks serves both masks
        assert flops.KERNEL_BLOCKS[kind] == (M._ATTN_KERNEL_BLOCKS["block_q"], M._ATTN_KERNEL_BLOCKS["block_kv"])


#: One key-value head at the published head size: 3 query heads to it under the window, 2 under the causal mask.
KERNEL_MODEL = dict(hidden_size=64, head_dim=128, num_key_value_heads=1, rope_parameters=ROPE, sliding_window=96)


@pytest.mark.parametrize("kind,heads,window", [("sliding_attention", 3, 96), ("sliding_attention", 3, 128), ("full_attention", 2, 96)])
def test_attention_and_every_gradient_by_the_fused_core_at_its_own_group(kind, heads, window, kernel_on_the_cpu, small_kernel_blocks):
    """``_attention`` whole with the kernel interpreted (two blocks a side), float32, against
    ``reference.attention`` a sequence: the layer type's mask, rope on its share of a head, its own number of query
    heads to the key-value head, the gate a head; the output within two bfloat16 steps of its size, the gradients of
    the input and of every weight within 1% in norm.  At a window of 128 the windowed layer's core runs banded (two
    chunks a sequence, three heads' rows a chunk), the gate a head made and applied in the core's chunks."""
    if kind == "sliding_attention":
        assert M._kernel_chunk(256, window, heads) == (128 if window == 128 else 0)
    cfg = M.Lfm2MoeConfig(hidden_size=64, head_dim=128, num_attention_heads=2, num_key_value_heads=1, qk_norm=False,
                          attn_head_gate=True, sliding_window=window, norm_eps=1e-6, seq_len=256, attn_block=64,
                          rope_parameters=F.rope_table(ROPE),
                          layer_types=("sliding_attention", "full_attention"), layer_ids=(0, 1),
                          num_attention_heads_per_layer=(3, 2), num_dense_layers=0)
    index = cfg.layer_types.index(kind)
    rng = np.random.default_rng([256, index])
    shapes = M.param_shapes(cfg)["layers"][index]["attn"]
    assert shapes["q"] == (64, heads * 128) and shapes["gate"] == (64, heads) and shapes["k"] == (64, 128)
    p = {name: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]), jnp.float32) for name, shape in shapes.items()}
    x = jnp.asarray(rng.normal(size=(2, 256, 64)), jnp.float32)
    got = F.value_and_gradients(lambda p, x: M._attention(p, x, cfg, jnp.float32, kind), p, x)
    with HIGHEST:
        want = F.value_and_gradients(
            lambda p, x: jnp.stack([R.attention(p, xs, {**KERNEL_MODEL, "sliding_window": window}, kind, heads, lambda a: a)
                                    for xs in x]), p, x)
    F.assert_within_bfloat16(got, want, ("q", "k", "v", "o", "gate"), floor=0.2)


# -- refusals, scopes, spans, counters ----------------------------------------------------------------------


_scopes = F.scopes


def test_each_layer_type_has_its_scope_with_proj_rope_core_and_gate_and_the_rules_class_them(tokens):
    cfg = A.config_of()
    w = jax.tree_util.tree_map(jnp.asarray, R.seeded_weights(CUT, 1, STD))
    scopes = _scopes(lambda p: M.forward(cfg, p, jnp.asarray(A.bias_of(CUT)), tokens[0][:2])[0], w)
    for layer, kind in enumerate(CUT["layer_types"]):
        for part in ("proj", "rope", "core", "gate"):
            assert any(s.startswith(f"layer{layer}/{kind}/{part}") for s in scopes), (layer, kind, part)
    assert any(s.startswith("layer0/dense_ffn") for s in scopes) and not any(s.startswith("layer0/moe") for s in scopes)
    for layer in (1, 2):
        for part in ("router", "dispatch", "experts", "combine", "shared"):
            assert any(s.startswith(f"layer{layer}/moe/{part}") for s in scopes), (layer, part)
    classify = scope_rules.classify
    assert classify("jit(lm_train_step)/transpose(jvp(layer2))/sliding_attention/core/splash") == ("window_core", "core")
    assert classify("jit(lm_train_step)/jvp(layer4)/full_attention/core/dot") == ("full_core", "core")
    assert classify("layer4/full_attention/rope/mul") == ("attention_proj", "rope")
    assert classify("layer1/sliding_attention/proj/dot_general") == ("attention_proj", "proj")
    assert classify("layer1/sliding_attention/gate/logistic") == ("attention_gate", "gate")
    assert classify("jit(lm_train_step)/jvp(layer0)/dense_ffn/dot_general") == ("dense_ffn", "layer0")
    assert classify("layer1/cond/branch_1_fun/moe/experts/gmm") == ("expert_mm", "experts")
    assert classify("layer1/moe/shared/dot_general") == ("shared_expert", "shared")
    assert classify("layer1/moe/router/dot") == ("moe_route", "router")
    assert classify("bias_update/sign") == ("optimizer", "bias_update") and classify("") == ("unattributed", "")
    assert {classify(s)[0] for s in scopes} <= set(scope_rules.CLASSES)
    assert {classify(s)[0] for s in scopes} >= {"window_core", "full_core", "attention_proj", "attention_gate", "dense_ffn",
                                               "shared_expert", "expert_mm", "moe_route", "head_loss"}


def _traced_individual(x, y, kw):
    with F.traced() as records:
        fitness = M.Lfm2MoeModel.cross_validate_population(x, y, [lfm2_moe_genome().default()], **kw)
    assert np.isfinite(fitness).all()
    trained = F.span_attrs(records, steps=kw["train_steps"])
    assert len(trained) == 1
    return trained[0], records


def test_the_train_span_states_each_masks_heads_and_rotated_columns_on_the_cpu_too(tokens):
    attrs, _ = _traced_individual(*tokens, A.model_kwargs(cache_dir=False))
    assert attrs["attention_heads_causal"] == [4, 4] and attrs["attention_heads_window"] == [6]
    assert attrs["attention_rotary_columns_causal"] == [8, 8] and attrs["attention_rotary_columns_window"] == [16]
    assert attrs["attention_kernel_layer_steps"] == 0 and attrs["attention_kernel_layer_steps_window"] == 0
    assert "attention_kernel_pairs_window" not in attrs, "no kernel, no table of visits"


def test_one_program_holds_the_kernel_at_two_groups_under_two_masks_and_the_spans_and_the_counter_say_so(kernel_on_the_cpu, small_kernel_blocks):
    """The cut's three layers at the published head size over 256 positions with a window of 128, the kernel
    interpreted: 1 windowed layer at 3 query heads to the key-value head and 2 full ones at 2, 2 steps.  The windowed
    layer's core runs banded, two chunks of 128 a sequence, and the span says so: the chunk, and a head's visits
    as ``length x (chunk + window)`` elements, forward and backward."""
    m = {**CUT, "head_dim": 128, "num_key_value_heads": 1, "num_attention_heads_per_layer": [2, 3, 2], "sliding_window": 128,
         "train_steps": 2}
    tok = np.random.default_rng(1).integers(0, 64, size=(6, 257)).astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    kw = A.model_kwargs(m, compute_dtype="bfloat16", attn_block=128, cache_dir=False)
    programs = M.Lfm2MoeModel.compiled_programs(x, **kw)
    assert programs.attention_kernel_layers == 3 and programs.kernel_layers_by_mask == (("causal", 2), ("window", 1))
    assert programs.heads_by_mask == (("causal", (2, 2)), ("window", (3,)))
    assert programs.rotary_by_mask == (("causal", (64, 64)), ("window", (128,)))
    visits = {mask: dict(v) for mask, v in programs.kernel_visits}
    assert visits["causal"]["pairs"] == 3 and "chunk" not in visits["causal"]  # of 4 at 2 x 2 blocks of 128
    assert visits["window"] == {"chunk": 128, "pairs": 2, "elements": 256 * 256, "pairs_bwd": 2, "elements_bwd": 256 * 256}
    attrs, _ = _traced_individual(x, y, kw)
    assert attrs["attention_kernel_layer_steps"] == 6 and attrs["attention_kernel_layer_steps_window"] == 2 \
        and attrs["attention_kernel_layer_steps_causal"] == 4
    assert attrs["attention_heads_causal"] == [2, 2] and attrs["attention_heads_window"] == [3]
    assert attrs["attention_kernel_elements_window"] == 256 * (128 + 128) == attrs["attention_kernel_elements_bwd_window"]
    assert attrs["attention_kernel_chunk_window"] == 128 and "attention_kernel_chunk_causal" not in attrs
    assert attrs["attention_kernel_elements_causal"] == 3 * 128 * 128
    counter = get_registry().counter
    assert counter("attention_kernel_layer_steps_total", mask="window").value == 2
    assert counter("attention_kernel_layer_steps_total", mask="causal").value == 4
    assert counter("dropped_assignments_total").value == 0


# -- the four architectures that were there ----------------------------------------------------------------------


#: (family, configuration): parameters, state bytes, activations by ``training_bytes`` at the parent commit (PR 41).
ACCEPTED = {"lfm2_moe": ("lfm2_24b_a2b_ep8", 647_819_520, 10_365_112_320, 2_852_126_720),
            "deepseek_v2": ("deepseek_v2_lite_ep8", 635_466_752, 10_167_468_032, 3_741_319_168),
            "mellum": ("mellum2_12b_a2p5b_ep8", 624_072_960, 9_985_167_360, 4_504_682_496),
            "qwen3_next": ("qwen3_next_80b_a3b_ep16", 625_667_136, 10_010_674_176, 6_111_100_928)}


_family_of, _published_cfg = F.family_module, F.published_cfg


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_an_accepted_configuration_builds_the_tree_and_the_bytes_it_built(name):
    config_name, n_params, state, activations = ACCEPTED[name]
    _, _, cfg = _published_cfg(name, config_name)
    need = M.training_bytes(cfg)
    assert (need["params"], need["state"], need["activations"]) == (n_params, state, activations)
    assert cfg.num_attention_heads_per_layer is None and not cfg.attn_head_gate and cfg.routed_scaling_factor == 1.0
    assert all(cfg.heads_of(i) == cfg.num_attention_heads for i in range(len(cfg.layer_types)))
    assert all(cfg.rotary_of(kind) == cfg.rotary_dim for kind in cfg.layer_types if kind in M.ATTENTION_KINDS)
    leaves = {jax.tree_util.keystr(p).split("]")[-2] for p, _ in
              jax.tree_util.tree_flatten_with_path(M.param_shapes(cfg), is_leaf=M._is_shape)[0]}
    assert "['gate'" not in leaves, leaves


# -- the benchmark's family: configuration file, counts, readers ------------------------------------------------


def _config_file():
    return F.config_file("laguna_xs2_ep8")


def test_the_configuration_file_holds_the_catalogs_numbers_and_the_cut_is_the_bytes_it_says():
    config, family, cfg = _published_cfg("laguna", "laguna_xs2_ep8")
    published = dict(hidden_size=2048, head_dim=128, num_attention_heads=48, num_key_value_heads=8, intermediate_size=8192,
                     moe_intermediate_size=512, shared_expert_intermediate_size=512, num_experts=256, num_experts_per_tok=8,
                     sliding_window=512, max_position_embeddings=262144, rms_norm_eps=1e-6, partial_rotary_factor=0.5,
                     moe_routed_scaling_factor=2.5, gating=True, model_type="laguna")
    for key, value in published.items():
        assert config[key] == value, key
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert config["layer_types"] == period * 10 and config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert config["rope_parameters"] == {**ROPE, "original_max_position_embeddings": 4096}
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "train_steps", "n_sequences"}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] == 100352 and config["num_hidden_layers"] == 5
    assert {"gating", "router", "qk_norm"} <= set(config["assumed"]) and "33.44 B" in config["published"]["parameters"]
    m = family.model_block(config)
    assert m["layer_types"] == period + ["full_attention"] and m["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert m["mlp_layer_types"] == ["dense"] + ["sparse"] * 4 and set(m["rope_parameters"]) == set(ROPE)
    need = M.training_bytes(cfg)
    assert need["params"] == 691_623_936 and need["state"] == 11_065_982_976
    assert "691,623,936" in config["published"]["this_chip"]
    assert cfg.tokens_per_step == 16384 and M._row_buffer_heights(cfg, 16384) == (20480, 45056, 131072)
    assert cfg.head_dim == 128 and not cfg.qk_norm and cfg.typed_attention and cfg.sliding_window == 512
    assert cfg.num_dense_layers == 1 and cfg.n_held == 32 and cfg.balance_rule == "bias" and cfg.scoring_func == "sigmoid"
    assert (cfg.rotary_of("full_attention"), cfg.rotary_of("sliding_attention")) == (64, 128)
    shapes = M.param_shapes(cfg)
    assert shapes["layers"][0]["attn"] == {"q": (2048, 6144), "k": (2048, 1024), "v": (2048, 1024), "o": (6144, 2048),
                                           "gate": (2048, 48)}
    assert shapes["layers"][1]["attn"]["q"] == (2048, 8192) and shapes["layers"][1]["attn"]["gate"] == (2048, 64)
    assert shapes["layers"][0]["dense"]["w1"] == (2048, 8192) and "moe" not in shapes["layers"][0]
    assert shapes["layers"][3]["moe"]["w1"] == (32, 2048, 512) and shapes["layers"][3]["moe"]["shared"]["w2"] == (512, 2048)
    assert shapes["head"] == (12544, 2048)
    # the whole model by the same count: the parameters the row states
    whole = (2 * 100352 * 2048 + 10 * (29_360_128 + 98_304) + 30 * (37_748_736 + 131_072) + 50_331_648
             + 39 * (256 * 3_145_728 + 3_145_728 + 524_288) + 40 * 4096 + 2048)
    assert round(whole / 1e9, 2) == 33.44
    rows = 16384 * 8 / 8 * 4  # 16,384 rows on the rank a routed layer, 4 routed layers
    total = flops.train_flops(m, 16384, rows, 8192)
    assert 55e12 < total < 75e12, total


def test_the_cell_runs_the_accepted_mix_as_it_is_under_the_bias_rules_genome():
    config = _config_file()
    mix = F.traffic_mix()
    pools = {name: _family_of(name).make_pool(4, [int(mix["pool_seed"])], float(mix["pool_log10_lr_max"]))
             for name in ("laguna", "lfm2_moe")}
    pool = pools["laguna"]
    assert "pool_log10_lr_max" not in config and len(pool) == config["population"] == 4 and pool == pools["lfm2_moe"]
    assert pool[0] == {"log10_lr": -3.5, "warmup_frac": 0.25, "weight_decay": 0.1, "beta2": 0.95, "bias_step": 0.001}
    assert all(r["log10_lr"] <= -3.5 and 0.0 <= r["bias_step"] <= 0.01 for r in pool)
    family = _family_of("laguna")
    small = {**config, "n_sequences": 6, "data": {**config["data"], "seq_len": 16}}
    a, b = (family.make_inputs(small, mix, seed) for seed in (3, 2147484001))
    assert a["pool"] == pool and np.array_equal(a["check_x"][:, 1:], a["check_y"][:, :-1])
    assert not np.array_equal(a["check_x"], b["check_x"])
    # one fixed pool for the window (the rate followed the seed's routing by 2.3%: ``assumed.window_inputs``): every
    # seed times the same work, from the weights and tokens of ``window_seed``
    assert config["window_seed"] == 4200000205 and a["params"]["seed"] == config["window_seed"] % (2**31 - 1)
    assert np.array_equal(a["x"], b["x"]) and a["params"] == b["params"] and not np.array_equal(a["x"], a["check_x"])
    assert np.array_equal(a["x"], family.markov_tokens(small["data"], small["vocab_size"], 6, 16, config["window_seed"])[:, :-1])


@pytest.fixture()
def layer_metric():
    """A reader of ``benchmark/layer_metrics/`` by name, loaded as ``run.py`` loads it."""
    with F.as_run_py_loads(A.family) as load:
        yield lambda name: load(f"layer_metrics/{name}")


_span = F.span


def test_the_kernel_readers_split_the_train_spans_by_mask_and_the_parent_reads_nothing(layer_metric):
    """The accepted mixed-attention readers (``layer_metrics/mel_*``) with this family's helper under their name."""
    window_reader, full_reader = layer_metric("mel_window_kernel_layer_steps"), layer_metric("mel_full_kernel_layer_steps")
    train = lambda t, **attrs: _span("train", t, {"individual": 0, "steps": 8, **attrs})
    run = {"window": (10.0, 20.0), "config": _config_file()}
    records = [train(5.0, attention_kernel_layer_steps_window=0, attention_kernel_layer_steps_causal=0),  # set-up
               train(11.0, attention_kernel_layer_steps_window=24, attention_kernel_layer_steps_causal=16,
                     attention_heads_window=[64, 64, 64], attention_heads_causal=[48, 48],
                     **{f"attention_kernel_{name}_window": n for name, n in
                        dict(pairs=15, elements=15 * 2**20, pairs_bwd=15, elements_bwd=15 * 2**20).items()}),
               train(12.0, attention_kernel_layer_steps_window=24, attention_kernel_layer_steps_causal=16),
               _span("train", 13.0, {"fold": 0, "attention_kernel_layer_steps_window": 99})]  # no span of this family
    assert window_reader.read({**run, "records": records}) == 24
    assert full_reader.read({**run, "records": records}) == 16
    assert window_reader.read({**run, "records": [train(11.0, attention_kernel_layer_steps=40)]}) is None  # the parent
    assert full_reader.read({**run, "records": records[:1]}) is None
    helper = sys.modules["mel_spans"]
    assert os.path.dirname(helper.__file__) == A.directory, "the readers' helper is this family's"
    assert helper.core_heads({**run, "records": records}, "sliding_attention") == [64, 64, 64]
    assert helper.core_visits({**run, "records": records}, "sliding_attention")["pairs"] == 15
    assert helper.core_visits({**run, "records": records}, "full_attention") is None
    assert helper.core_heads({**run, "records": records[:1]}, "full_attention") is None
