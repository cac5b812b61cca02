"""What is Nemotron-H's own among the routed family's tests (the sixth architecture through
``models/lfm2_moe.py``, against ``benchmark/families/nemotron_h/reference.py``, at small sizes on the CPU); what every
architecture is held to (logits, loss and every gradient under a router bias that changes the choice: a block of each
kind, the cut whole, two periods; two train steps with the bias's step; the 64 expert shares of the scaled routed sum
with the latent projections and the shared expert counted once; the ladder at 22 a token and 8 held; refusals; the
manifest's readers; the scope rules) is in ``test_routed_family*.py`` under ``nemotron_h-`` ids.

Here: the chunked core against the recurrence one position at a time, over several chunks, at lengths that are and are
not whole chunks, under slow and fast decays, the state crossing a boundary; ``_affine_scan`` under a scalar decay
against the same decay as a matrix; the eight head shares adding up to the uncut mixer of the reference, and B and C
of the wrong group seen there; the start of a Mamba-2 block's leaves and which take no weight decay; a block's one
norm; the pattern's letters; the scopes, the spans, the counter; the configuration file, the counts and the accepted
readers that read the new cell.
"""

from __future__ import annotations

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry.registry import get_registry
from routed_family import HIGHEST

A = F.ARCHS["nemotron_h"]
R, flops = A.R, A.flops
CELL, CONFIG = "nemotron3_super_120b_a12b_ep64.popeval", "nemotron3_super_120b_a12b_ep64"
CUT = A.model
IDENTITY = lambda a: a


# -- the core: chunks against the recurrence ------------------------------------------------------------------------


def _core_operands(length, seed, rate_range, groups=2, per_group=2, size=8, state=6):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(2, length, groups, per_group, size))
    b, c = rng.normal(size=(2, 2, length, groups, state))
    step = np.exp(rng.uniform(math.log(1e-3), math.log(0.1), size=(2, length, groups, per_group)))
    rate = -rng.uniform(*rate_range, size=(groups, per_group))
    return tuple(jnp.asarray(a, jnp.float32) for a in (x, b, c, step, rate))


def _by_the_recurrence(x, b, c, step, rate):
    s, length, groups, per_group, size = x.shape
    heads = groups * per_group
    flat = lambda a: a.reshape(length, heads, *a.shape[3:])
    rows = []
    for i in range(s):
        bi, ci = (jnp.repeat(a[i], per_group, axis=1) for a in (b, c))
        decay = jnp.exp(flat(step[i]) * rate.reshape(heads))
        rows.append(R.recurrence(flat(x[i]), bi, ci, flat(step[i]), decay).reshape(length, groups, per_group, size))
    return jnp.stack(rows)


@pytest.mark.parametrize("length", [24, 29, 8, 5])  # three chunks of 8, three and a half, one, less than one
@pytest.mark.parametrize("decays,rate_range", [("slow", (1.0, 2.0)), ("fast", (100.0, 1600.0)), ("published", (1.0, 16.0))])
def test_the_chunked_core_is_the_recurrence(length, decays, rate_range):
    """Value and every gradient.  Slow: a step of 0.001-0.1 at a rate of 1-2 keeps a state for hundreds of
    positions, so what crosses every boundary is most of the output; fast: a state forgotten within a position."""
    operands = _core_operands(length, 3, rate_range)
    probe = jnp.asarray(np.random.default_rng(1).normal(size=operands[0].shape), jnp.float32)
    with HIGHEST:
        got, grads = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(M._state_space_core(*a, 8) * probe), argnums=(0, 1, 2, 3, 4)))(*operands)
        want, ref_grads = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(_by_the_recurrence(*a) * probe), argnums=(0, 1, 2, 3, 4)))(*operands)
        out, ref_out = M._state_space_core(*operands, 8), _by_the_recurrence(*operands)
    np.testing.assert_allclose(out, ref_out, atol=2e-6 * float(jnp.abs(ref_out).max()))
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for g, r, name in zip(grads, ref_grads, ("x", "B", "C", "step", "rate")):
        assert float(jnp.abs(r).max()) > 0, name
        np.testing.assert_allclose(g, r, atol=2e-5 * float(jnp.abs(r).max()), err_msg=name)


def test_the_state_crosses_a_chunk_boundary_and_a_core_that_drops_it_is_seen(monkeypatch):
    operands = _core_operands(24, 5, (1.0, 2.0))
    with HIGHEST:
        whole = M._state_space_core(*operands, 8)
        monkeypatch.setattr(M, "_affine_scan", lambda a, b: jnp.zeros_like(b))
        reset = M._state_space_core(*operands, 8)
    np.testing.assert_allclose(whole[:, :8], reset[:, :8], atol=1e-6)  # the first chunk enters from nothing either way
    assert F.rel(reset[:, 8:], whole[:, 8:]) > 0.3


def test_the_scan_under_a_scalar_decay_is_the_scan_under_that_decay_as_a_matrix():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.uniform(0.2, 1.0, size=(5, 3, 2)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(5, 3, 2, 4, 6)), jnp.float32)
    as_matrix = a[..., None, None] * jnp.eye(4, dtype=jnp.float32)
    probe = jnp.asarray(rng.normal(size=b.shape), jnp.float32)
    value = lambda a, b: jnp.sum(M._affine_scan(a, b) * probe)
    with HIGHEST:
        (got, (da, db)), (want, (dm, dbm)) = (jax.value_and_grad(value, argnums=(0, 1))(*pair) for pair in ((a, b), (as_matrix, b)))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(db, dbm, atol=1e-5)
    np.testing.assert_allclose(da, jnp.trace(dm, axis1=-2, axis2=-1), atol=1e-4)  # the gradient of a * I along I


# -- the mixer held by its heads' share -----------------------------------------------------------------------------

EIGHT_GROUPS = {**CUT, "mamba_num_heads": 16, "n_groups": 8, "held_mamba_heads": [0, 16]}


def _share_of_the_mixer(w, m, first, last):
    """The leaves of heads [first, last) -- whole groups -- of the uncut mixer ``w`` of the model ``m``."""
    size, state, per_group = m["mamba_head_dim"], m["ssm_state_size"], m["mamba_num_heads"] // m["n_groups"]
    inner, bc = m["mamba_num_heads"] * size, m["n_groups"] * state
    heads = np.arange(first, last)
    channels = (heads[:, None] * size + np.arange(size)).ravel()
    groups = np.arange(first // per_group, last // per_group)
    of_groups = (groups[:, None] * state + np.arange(state)).ravel()
    mixed = np.concatenate([channels, inner + of_groups, inner + bc + of_groups])  # x, B, C as the convolution sees them
    columns = np.concatenate([channels, inner + mixed, 2 * inner + 2 * bc + heads])  # z | x B C | dt
    return {"in_proj": w["in_proj"][:, columns], "kernel": w["kernel"][mixed], "conv_bias": w["conv_bias"][mixed],
            "A_log": w["A_log"][heads], "D": w["D"][heads], "dt_bias": w["dt_bias"][heads], "norm": w["norm"][channels],
            "out": w["out"][channels]}


def test_the_eight_head_shares_add_up_to_the_uncut_mixer_of_the_reference():
    """16 heads in 8 groups of 2, a share a group: the shares' outputs, each ``W_out[its rows] y_share``, add up to
    the reference's uncut mixer; the norm is a group's, so no share needs another's channels."""
    m = {**F.nmh_blocks("mamba2"), **{k: EIGHT_GROUPS[k] for k in ("mamba_num_heads", "n_groups", "held_mamba_heads")}}
    w = jax.tree_util.tree_map(jnp.asarray, A.seeded_weights(m, 11)["layers"][0]["mamba"])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 28, 40)), jnp.float32)
    with HIGHEST:
        whole = jnp.stack([R.mamba2(w, xs, m, IDENTITY) for xs in x])
        total = jnp.zeros_like(whole)
        for first in range(0, 16, 2):
            cfg = A.config_of({**m, "held_mamba_heads": [first, first + 2]})
            part = jax.jit(lambda p, x: M._state_space(p, x, cfg, jnp.float32))(_share_of_the_mixer(w, m, first, first + 2), x)
            assert float(jnp.abs(part).max()) > 1e-3
            total = total + part
            one = jnp.stack([R.mamba2(_share_of_the_mixer(w, m, first, first + 2), xs, {**m, "held_mamba_heads": [first, first + 2]},
                                      IDENTITY) for xs in x])
            np.testing.assert_allclose(part, one, atol=3e-6)  # and each is the reference's share
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_b_and_c_of_the_wrong_group_are_seen_on_the_uncut_mixer(monkeypatch):
    """Where one group is held nothing can read another's B and C; on the uncut layer a head that reads its
    neighbouring group's differs from the reference."""
    m = {**F.nmh_blocks("mamba2"), "held_mamba_heads": [0, 4]}
    cfg = A.config_of(m)
    w = jax.tree_util.tree_map(jnp.asarray, A.seeded_weights(m, 11)["layers"][0]["mamba"])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 28, 40)), jnp.float32)
    real = M._state_space_core
    with HIGHEST:
        want = jnp.stack([R.mamba2(w, xs, m, IDENTITY) for xs in x])
        np.testing.assert_allclose(M._state_space(w, x, cfg, jnp.float32), want, atol=3e-6)
        monkeypatch.setattr(M, "_state_space_core", lambda x, b, c, *rest: real(x, jnp.roll(b, 1, axis=2), jnp.roll(c, 1, axis=2), *rest))
        assert F.rel(M._state_space(w, x, cfg, jnp.float32), want) > 0.05


# -- a block, its leaves and their start ----------------------------------------------------------------------------


def test_a_block_has_one_norm_and_the_other_architectures_blocks_two():
    shapes = M.param_shapes(A.config_of())
    assert [sorted(k for k in layer if "norm" in k) for layer in shapes["layers"]] == \
        [["op_norm"], ["ffn_norm"], ["op_norm"], ["ffn_norm"], ["op_norm"]]
    assert shapes["layers"][1]["moe"]["w1"] == (2, 16, 24) and shapes["layers"][1]["moe"]["w2"] == (2, 24, 16)
    assert "w3" not in shapes["layers"][1]["moe"] and sorted(shapes["layers"][1]["moe"]["shared"]) == ["w1", "w2"]
    assert shapes["layers"][0]["mamba"]["in_proj"] == (40, 16 + 16 + 2 * 6 + 2)  # z, x, one group's B and C, a dt a head
    for name in ("lfm2_moe", "laguna", "qwen3_next"):
        other = M.param_shapes(F.ARCHS[name].config_of())
        assert all({"op_norm", "ffn_norm"} <= set(layer) for layer in other["layers"]), name


def test_a_mamba2_blocks_leaves_start_as_the_model_types_initialiser_has_them_and_take_no_weight_decay():
    programs = M.Lfm2MoeModel.compiled_programs(A.tokens[0], **A.model_kwargs({**CUT, "mamba_num_heads": 64, "n_groups": 2,
                                                                              "held_mamba_heads": [0, 32]}))
    params = programs.init(jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))["params"]
    p = params["layers"][0]["mamba"]
    rates, steps = np.exp(p["A_log"]), np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert rates.min() >= 1.0 and rates.max() <= 16.0 and rates.std() > 2.0
    assert steps.min() >= 1e-3 * 0.999 and steps.max() <= 0.1 * 1.001 and np.log(steps).std() > 0.8
    assert (np.asarray(p["D"]) == 1).all() and (np.asarray(p["norm"]) == 1).all() and not np.asarray(p["conv_bias"]).any()
    assert 0.015 < float(jnp.std(p["in_proj"])) < 0.025 and 0.01 < float(jnp.std(p["kernel"])) < 0.03
    assert (np.asarray(params["layers"][1]["ffn_norm"]) == 1).all()
    undecayed = {jax.tree_util.keystr(path[-1:]) for path, _ in jax.tree_util.tree_flatten_with_path(params["layers"][0])[0]
                 if any(name in str(path[-1]) for name in M._UNDECAYED)}
    assert undecayed == {"['A_log']", "['D']", "['conv_bias']", "['dt_bias']", "['norm']", "['op_norm']"}
    assert undecayed == {jax.tree_util.keystr(path[-1:]) for path, _ in jax.tree_util.tree_flatten_with_path(params["layers"][0])[0]
                         if any(name in str(path[-1]) for name in R.UNDECAYED)}
    # the fourth architecture's leaves of the same names keep their own start
    q3n = M.Lfm2MoeModel.compiled_programs(F.ARCHS["qwen3_next"].tokens[0], **F.ARCHS["qwen3_next"].model_kwargs())
    delta = q3n.init(jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))["params"]["layers"][0]["delta"]
    assert (np.asarray(delta["dt_bias"]) == 1).all() and float(np.exp(delta["A_log"]).min()) < 16.0


def test_the_patterns_letters_are_the_blocks_and_an_unknown_letter_is_refused():
    family = F.family_module("nemotron_h")
    config = F.config_file(CONFIG)
    assert family.layer_types(config["hybrid_override_pattern"], config["layers_kept"]) == \
        [family.PATTERN[c] for c in "MEMEMEM*EME"]
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 88 and (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (40, 40, 8)
    with pytest.raises(ValueError, match=r"letters \['-'\]"):
        family.layer_types("ME-*", [0, 1])


# -- scopes, spans, the counter --------------------------------------------------------------------------------------


def test_the_blocks_work_lies_under_their_scopes():
    cfg = A.config_of()
    w = jax.tree_util.tree_map(jnp.asarray, A.seeded_weights(CUT, 3))
    bias = jnp.zeros((2, 16), jnp.float32)
    found = F.scopes(lambda p: M.forward(cfg, p, bias, A.tokens[0][:2])[0], w)
    for inside in ("proj", "conv", "gates", "core", "norm_gate"):
        assert any(f"layer0/mamba2/{inside}" in s for s in found), inside
    for inside in ("router", "latent_in", "dispatch", "experts", "combine", "latent_out", "shared"):
        assert any(f"layer1/moe/{inside}" in s for s in found) or any(f"layer1/" in s and f"/moe/{inside}" in s for s in found), inside
    assert any("layer2/full_attention/core" in s for s in found) and any("layer2/full_attention/proj" in s for s in found)
    assert not any("rope" in s.split("/")[-1] and "cos" in s for s in found)
    assert all(A.scope_rules.classify(s)[0] in A.scope_rules.CLASSES for s in found)


def test_the_train_span_and_the_registry_say_what_the_state_space_core_ran_as():
    x, y = A.tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **A.model_kwargs())
    assert programs.state_space_core_layers == (("chunked", 2),) and programs.linear_core_layers == ()
    assert dict(programs.rotary_by_mask) == {"causal": (0,)}  # no positional encoding: rope turns no column
    with F.traced() as records:
        F.score_one(programs, x, y, A.genes)
        counted = get_registry().counter("state_space_core_layer_steps_total", program="chunked").value
    (train,) = F.span_attrs(records, steps=3)
    assert train["state_space_core_layer_steps_chunked"] == 2 * 3 == counted
    assert train["state_space_core_chunk"] == 8 and train["state_space_heads_held"] == 2 and train["latent_experts_width"] == 16
    assert "linear_core_chunk" not in train
    (fetch,) = F.span_attrs(records, "fetch")
    assert np.asarray(fetch["expert_rows"]).shape == (2, 2) and fetch["dropped"] == 0
    # a configuration without such layers says nothing of them
    other = F.ARCHS["laguna"]
    with F.traced() as records:
        F.score_one(M.Lfm2MoeModel.compiled_programs(other.tokens[0], **other.model_kwargs()), *other.tokens, other.genes)
    (train,) = F.span_attrs(records, steps=3)
    assert not [k for k in train if k.startswith(("state_space", "latent_experts"))]


# -- the configuration file, the counts, the readers -----------------------------------------------------------------


@pytest.fixture(scope="module")
def published():
    return F.published_cfg("nemotron_h", CONFIG)


def test_the_published_cut_counts_the_parameters_the_configuration_states(published):
    config, family, cfg = published
    need = M.training_bytes(cfg)
    assert need["params"] == 731_271_664 and f"{need['params']:,}" in config["published"]["this_chip"]
    assert 11.6e9 < need["state"] < 11.8e9 and 14.0e9 < need["total"] < 15.0e9
    shapes = M.param_shapes(cfg)
    count = lambda tree: sum(math.prod(s) for s in jax.tree_util.tree_leaves(tree, is_leaf=M._is_shape))
    assert [count(layer) for layer in shapes["layers"]] == [
        {"mamba2": 13_708_592, "routed": 98_570_240, "full_attention": 35_655_680}[kind] for kind in cfg.layer_types]
    assert cfg.layer_types == tuple(family.PATTERN[c] for c in "MEMEMEM*EME") and cfg.moe_layers == (1, 3, 5, 8, 10)
    assert (cfg.mamba_held, M._mamba_widths(cfg)) == ((16, 1), (1024, 1280)) and cfg.held_experts == (0, 8)
    assert M._row_buffer_heights(cfg, cfg.tokens_per_step) == (3584, 8192, 8 * 8192)  # min(22, 8) rows a token at the worst
    assert cfg.tokens_per_step == 8192 and cfg.route_eps == 1e-20 and cfg.routed_scaling_factor == 5
    # every width as published; the catalog's numbers under their keys, but for what ``reduced`` names
    entry = next(c for c in F.manifest()["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(config["reduced"]) and len(entry["source"]) <= 200
    for key, value in {"hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
                       "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
                       "n_routed_experts": 512, "num_experts_per_tok": 22, "moe_latent_size": 1024, "moe_intermediate_size": 2688,
                       "moe_shared_expert_intermediate_size": 5376, "routed_scaling_factor": 5, "expand": 2}.items():
        assert config[key] == value and key not in config["reduced"], key


def test_the_counts_follow_the_kernels_table_and_the_reference_takes_the_models_block(published):
    config, family, cfg = published
    m = family.model_block(config)
    visits = M._kernel_visits(8192, None, M._core_columns(128, 128))
    assert flops.block_visits(8192) == visits and visits["pairs"] == 36
    assert flops.mamba_held(m) == (16, 1) and flops.layers_of(m, "mamba2") == 5 == flops.layers_of(m, "routed")
    # a step of 8,192 tokens: the products outside the cores and the experts, four passes, and the head, three
    step = flops.train_flops(m, 8192, 2816 * 5, 8192)
    assert 30e12 < step < 33e12  # 0.25 PFLOP an individual of 8 steps
    assert R.mamba_share(m) == (16, 1, 1024, 128) and R.routed_layers(m) == [1, 3, 5, 8, 10]


def test_the_accepted_readers_read_this_architectures_spans_under_its_own_names():
    config = F.config_file(CONFIG)
    attrs = {"individual": 0, "steps": 8, "tokens": 65536, "state_space_core_layer_steps_chunked": 40,
             "state_space_core_chunk": 128, "attention_kernel_layer_steps_causal": 8}
    run = {**F.empty_run(config, CELL), "records": [F.span("train", 0.5, attrs)]}
    with F.as_run_py_loads("nemotron_h") as load:
        spans = load("q3n_spans")
        assert spans.delta_chunk(run) == 128 and spans.delta_chunk(F.empty_run(config, CELL)) is None
        assert load("layer_metrics/q3n_delta_chunked_layer_steps").read(run) == 40
        assert load("layer_metrics/q3n_full_kernel_layer_steps").read(run) == 8
        assert load("layer_metrics/q3n_delta_core_roofline_share").read(run) is None  # no trace: nothing to divide by
