"""Every architecture of the routed family (``models/lfm2_moe.py``) against its plain reference
(``benchmark/families/<name>/reference.py``), at small sizes on the CPU: one comparison a property, written once;
``test_routed_family*.py`` run them over each architecture's own cases (``routed_family.ARCHS``), under ids that
name the architecture, in files small enough for ``--dist loadfile`` to spread.

System and reference are compared in float32 on seeded weights: per layer kind and whole on logits, loss (with the
balance term where the architecture has one) and every gradient; over two train steps on loss, load, parameter
change, the router bias's step or the balance term, and the held-out loss; and the share test ties the expert
layer's cut (``held_experts``) to the uncut layer, with what every share computes alike counted once.  What only
one architecture has (latent attention's one rope head, the window's mask object, the delta rule against the
recurrence, the gate a head) is in that architecture's file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import routed_family as F
from gentun_tpu.models import lfm2_moe as M
from routed_family import ARCHS, HIGHEST, ROWS

IDENTITY = lambda a: a


# -- what a case asserts of its own architecture, before and after the comparison --------------------------------------


def _no_layer_took_the_wide_buffer(arch, cfg, m, bias, load, stats, forward):
    assert int(stats.wide) == 0


def _mellum_tree(arch, cfg, m, w):
    assert cfg.head_dim == 16 != cfg.hidden_size // cfg.num_attention_heads and cfg.num_dense_layers == 0
    assert "q_norm" not in w["layers"][0]["attn"] and M.param_shapes(cfg)["layers"][0]["attn"].keys() == \
        w["layers"][0]["attn"].keys()


def _q3n_tree(arch, cfg, m, w):
    assert cfg.typed_attention == ("linear_attention" in m["layer_types"]) and cfg.rotary_dim == 4
    shapes = M.param_shapes(cfg)
    assert [a.shape for a in jax.tree_util.tree_leaves(w)] == jax.tree_util.tree_leaves(shapes, is_leaf=M._is_shape)
    assert jax.tree_util.tree_structure(w) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, shapes, is_leaf=M._is_shape))


def _laguna_tree(arch, cfg, m, w):
    assert cfg.head_dim == 16 and cfg.attn_head_gate and not cfg.attn_output_gate and cfg.routed_scaling_factor == 2.5
    assert [cfg.heads_of(i) for i in range(len(cfg.layer_types))] == m["num_attention_heads_per_layer"]
    shapes = M.param_shapes(cfg)
    assert jax.tree_util.tree_map(lambda a: a.shape, w) == jax.tree_util.tree_map(lambda s: s, shapes, is_leaf=M._is_shape)


def _laguna_outcome(arch, cfg, m, bias, load, stats, forward):
    unbiased = forward(jnp.zeros_like(bias))
    assert not np.array_equal(load, unbiased), "the bias changes the choice"
    assert float(stats.balance) == 0.0


def _nmh_tree(arch, cfg, m, w):
    assert cfg.single_half_layers and not cfg.gated_experts and cfg.positional_encoding == "none" and cfg.typed_attention
    assert cfg.mamba_heads == tuple(m["held_mamba_heads"]) and cfg.moe_layers == tuple(
        i for i, kind in enumerate(m["layer_types"]) if kind == "routed")
    shapes = M.param_shapes(cfg)
    assert jax.tree_util.tree_map(lambda a: a.shape, w) == jax.tree_util.tree_map(lambda s: s, shapes, is_leaf=M._is_shape)
    # a block has ONE norm and ONE of a mixer and a feed-forward
    for layer, kind in zip(shapes["layers"], m["layer_types"]):
        assert sorted(layer) == {"mamba2": ["mamba", "op_norm"], "full_attention": ["attn", "op_norm"],
                                 "routed": ["ffn_norm", "moe"]}[kind]


def _keye_tree(arch, cfg, m, w):
    assert cfg.typed_attention and cfg.sparse_layers == tuple(range(m["num_hidden_layers"])) and cfg.qk_norm
    assert cfg.sparse_topk == m["topk"] and cfg.mrope_section == tuple(m["mrope_section"])
    shapes = M.param_shapes(cfg)
    assert jax.tree_util.tree_map(lambda a: a.shape, w) == jax.tree_util.tree_map(lambda s: s, shapes, is_leaf=M._is_shape)
    assert sorted(shapes["layers"][0]) == ["attn", "ffn_norm", "indexer", "moe", "op_norm"]


def _keye_outcome(arch, cfg, m, bias, load, stats, forward):
    """The pairs each layer kept are the arithmetic's (no two scores tie): every key of a query with no more than
    ``topk`` of them, ``topk`` of every other's; and the indexers' losses are a positive number."""
    length, top = arch.positions, min(m["topk"], arch.positions)
    per_sequence = top * (top + 1) // 2 + (length - top) * top
    np.testing.assert_array_equal(stats.selected, [2 * per_sequence] * m["num_hidden_layers"])
    assert float(stats.indexer_loss) > 0


#: architecture: (its assertions on the configuration and the tree, its assertions on the outcome)
OWN = {"lfm2_moe": (None, _no_layer_took_the_wide_buffer), "deepseek_v2": (None, _no_layer_took_the_wide_buffer),
       "mellum2": (_mellum_tree, None),
       "qwen3_next": (_q3n_tree, None), "laguna": (_laguna_tree, _laguna_outcome),
       "nemotron_h": (_nmh_tree, _laguna_outcome), "keye_vl2": (_keye_tree, _keye_outcome)}
#: the parity test's router bias, where a rule reads one: (seed, deviation)
PARITY_BIAS = {"lfm2_moe": (2, 0.1), "laguna": (3, 0.2), "nemotron_h": (3, 0.2)}
STEP_BIAS = {"lfm2_moe": (1, 0.1), "laguna": (3, 0.2), "nemotron_h": (3, 0.2)}
NO_BIAS = jnp.zeros((8, 8), jnp.float32)  # the state's bias: zeros, never read under the ``aux_loss`` rule
ALPHA = 0.05  # the balance term's weight in the compared loss


def _reference_forward(arch, m, params, bias, tokens):
    """(logits, load, balance or None) of the reference on one sequence."""
    if arch.rule == "bias":
        logits, load = arch.R.forward(m, params, bias, tokens)[:2]
        return logits, load, None
    return arch.R.forward(m, params, tokens)  # a fourth entry where the model has an indexer: (its losses summed, the pairs kept)


def logits_loss_and_every_gradient_match_the_reference(name, case):
    arch = ARCHS[name]
    R, m = arch.R, arch.layer_cases[case]
    cfg = arch.config_of(m)
    w = arch.seeded_weights(m, 7)
    check_tree, check_outcome = OWN[name]
    if check_tree is not None:
        check_tree(arch, cfg, m, w)
    x, y = arch.tokens[0][:2], arch.tokens[1][:2]
    balanced = arch.rule == "aux_loss"
    bias = NO_BIAS if balanced else jnp.asarray(arch.bias_of(m, *PARITY_BIAS[name]))

    def system_loss(params):
        logits, load, stats = M.forward(cfg, params, bias, x, remat=True)
        nll = M.token_loss(logits, y).mean()
        if stats.indexer_loss is not None:  # the indexers' own term, at a weight of 1
            nll = nll + stats.indexer_loss
        return (nll + ALPHA * stats.balance if balanced else nll), (logits, load, stats)

    def reference_loss(params):
        out = [_reference_forward(arch, m, params, bias, xs) for xs in x]
        logits = jnp.stack([o[0] for o in out])
        nll = jnp.mean(jnp.stack([R.token_loss(l, ys) for l, ys in zip(logits, y)]))
        balance = sum(o[2] for o in out) / len(out) if balanced else 0.0
        if len(out[0]) > 3:
            nll = nll + sum(o[3][0] for o in out) / len(out)
        return (nll + ALPHA * balance if balanced else nll), (logits, sum(o[1] for o in out), balance)

    with HIGHEST:
        (loss, (logits, load, stats)), grads = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(w)
        (ref_loss, (ref_logits, ref_load, ref_balance)), ref_grads = jax.jit(
            jax.value_and_grad(reference_loss, has_aux=True))(w)
        if check_outcome is not None:
            check_outcome(arch, cfg, m, bias, load, stats, jax.jit(lambda b: M.forward(cfg, w, b, x)[1]))
    np.testing.assert_allclose(logits, ref_logits, atol=arch.tolerance("logits"))
    np.testing.assert_allclose(loss, ref_loss, rtol=arch.tolerance("loss"))
    if balanced:
        np.testing.assert_allclose(stats.balance, ref_balance, rtol=arch.tolerance("loss"))
        assert float(ref_balance) > 0.9 * arch.routed_layers(m)  # ~1 a routed layer
    np.testing.assert_array_equal(load, ref_load)
    assert int(stats.dropped) == 0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.abs(r).max())
        atol = arch.tolerance("gradient") * (max(scale, 1.0) if arch.gradient_bound_follows_its_size else 1.0)
        np.testing.assert_allclose(g, r, atol=atol, rtol=1e-4, err_msg=jax.tree_util.keystr(path))
        assert scale > 0 or (not arch.tied_embeddings and "embed" in str(path)), \
            f"{jax.tree_util.keystr(path)}: the reference's gradient is all zero"


def _lfm2_step(arch, state, ref, bias):
    assert np.abs(np.asarray(state["bias"]) - bias).max() == pytest.approx(0.02, rel=1e-5)  # two steps of 0.01


def _laguna_step(arch, state, ref, bias):
    moved = np.abs(ref["bias"] - bias) / arch.genes["bias_step"]
    assert moved.max() == pytest.approx(2.0, abs=1e-3) and (moved[:, [0, 1, 5, 6, 7]] > 0.5).any(axis=0).all(), \
        "every expert's bias steps, held here or not"


def _nmh_step(arch, state, ref, bias):
    moved = np.abs(ref["bias"] - bias) / arch.genes["bias_step"]
    assert moved.max() == pytest.approx(2.0, abs=1e-3) and (moved > 0.5).any(axis=0).all(), "every expert's bias steps, held here or not"


OWN_STEP = {"lfm2_moe": _lfm2_step, "laguna": _laguna_step, "nemotron_h": _nmh_step}


def two_train_steps_match_the_reference(name):
    arch = ARCHS[name]
    R, m = arch.R, arch.step_model or arch.model
    x, y = arch.tokens
    programs = M.Lfm2MoeModel.compiled_programs(x, **arch.model_kwargs(m))
    assert programs.config.gene_names == tuple(F.genome_of(arch).names)
    balanced = arch.rule == "aux_loss"
    assert ("aux_loss" in jax.eval_shape(programs.init, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))) == balanced
    w = arch.seeded_weights(m, 5)
    bias = None if balanced else arch.bias_of(m, *STEP_BIAS[name])
    batches = [(x[r], y[r]) for r in ROWS[:2]]
    with HIGHEST:
        state, losses, loads = F.program_steps(programs, w, x, y, ROWS, 2, arch.genes, bias)
        ref = R.train(m, w, batches, arch.genes) if balanced else R.train(m, w, batches, arch.genes, bias=bias)
    np.testing.assert_allclose(losses, ref["losses"], rtol=arch.tolerance("loss"))  # the balance term included
    for got, want in zip(loads, ref["loads"]):
        np.testing.assert_array_equal(got, want[:, 2:4])
    np.testing.assert_array_equal(np.asarray(state["rows"]), sum(l[:, 2:4] for l in ref["loads"]))
    if balanced:
        np.testing.assert_allclose(float(state["aux_loss"]), sum(ref["balances"]), rtol=arch.tolerance("loss"))
        assert not np.asarray(state["bias"]).any(), "no bias and no rule outside the gradient"
    else:
        np.testing.assert_allclose(state["bias"], ref["bias"], atol=1e-7)
        OWN_STEP[name](arch, state, ref, bias)
    if "indexer_losses" in ref:  # the indexers' own term and the pairs they kept, summed on the device over the steps
        np.testing.assert_allclose(float(state["indexer_loss"]), sum(ref["indexer_losses"]), rtol=arch.tolerance("loss"))
        np.testing.assert_array_equal(np.asarray(state["selected_pairs"]), sum(ref["selected"]))
    for (path, a), b, start in zip(jax.tree_util.tree_flatten_with_path(state["params"])[0],
                                   jax.tree_util.tree_leaves(ref["weights"]), jax.tree_util.tree_leaves(w)):
        change, ref_change = np.asarray(a) - start, np.asarray(b) - start
        assert np.abs(ref_change).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(change, ref_change, atol=arch.tolerance("step"), err_msg=jax.tree_util.keystr(path))
    held_out = (x[8:10], y[8:10])
    with HIGHEST:
        got = programs.eval(state["params"], state["bias"], jnp.asarray(x), jnp.asarray(y), jnp.asarray([8, 9]))
        want = R.eval_token_loss(m, ref["weights"], *held_out) if balanced else \
            R.eval_token_loss(m, ref["weights"], ref["bias"], *held_out)
    np.testing.assert_allclose(got, want, atol=arch.tolerance("eval"))


# -- the shares of the expert layer -------------------------------------------------------------------------------------


def _one_layer(arch, kind, **over):
    if arch.name == "nemotron_h":
        return {**F.nmh_blocks(kind), **over}
    if arch.name == "laguna":
        return F.laguna_one_layer(kind, {"sliding_attention": 6, "full_attention": 4}[kind], **over)
    return {**arch.model, "num_hidden_layers": 1, "layer_types": [kind], **over}


#: id: (architecture, the one-layer model, its experts[, the experts a share holds: 2 unless given])
SHARES = {
    # 8 experts in 4 shares of 2: operator, residual and each share's own experts' part
    "lfm2_moe-conv": ("lfm2_moe", F.lfm2_one_layer("conv", "moe"), 8),
    # 16 experts in 8 shares of 2, 6 a token, the shared experts counted once
    "deepseek_v2-latent": ("deepseek_v2", {**ARCHS["deepseek_v2"].model, "num_hidden_layers": 1, "first_k_dense_replace": 0,
                                           "n_routed_experts": 16, "num_experts_per_tok": 6}, 16),
    # 16 experts, 8 a token, the weights normalised over all the chosen eight, attention counted once
    **{f"mellum2-{kind}": ("mellum2", _one_layer(ARCHS["mellum2"], kind, num_experts=16, num_experts_per_tok=8), 16)
       for kind in ("sliding_attention", "full_attention")},
    # 8 experts, 5 a token; the mixer, the gated shared expert and its gate counted once
    **{f"qwen3_next-{kind}": ("qwen3_next", _one_layer(ARCHS["qwen3_next"], kind, num_experts=8, num_experts_per_tok=5), 8)
       for kind in F.Q3N_PERIOD[2:]},
    # 16 experts, 8 a token; each share 2.5 times its routed part, the shared expert once
    **{f"laguna-{kind}": ("laguna", _one_layer(ARCHS["laguna"], kind, num_experts=16, num_experts_per_tok=8), 16)
       for kind in ("sliding_attention", "full_attention")},
    # 128 experts, 22 a token (more than a share holds: a token reaches a held expert once), in 64 shares of 2; each
    # share 5 times its routed part formed in the latent state; the two latent projections and the shared expert once
    "nemotron_h-routed": ("nemotron_h", _one_layer(ARCHS["nemotron_h"], "routed", n_routed_experts=128,
                                                   num_experts_per_tok=22), 128),
    # 128 experts, 8 a token, in 8 shares of 16 (the cut's own numbers); attention, its indexer and the router once
    "keye_vl2-sparse_attention": ("keye_vl2", {**ARCHS["keye_vl2"].model, "num_hidden_layers": 1, "num_experts": 128,
                                               "num_experts_per_tok": 8}, 128, 16),
}


def the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(case):
    """Each share's program computes the operator, the residual, what is shared and its own routed experts' part;
    the routed parts, with what every share computes alike counted once, are the uncut reference's layer output."""
    name, m, experts, *share = SHARES[case]
    step = share[0] if share else 2  # the experts a share holds
    arch = ARCHS[name]
    R, x = arch.R, arch.tokens[0][:2]
    uncut = {**m, "held_experts": [0, experts]}
    w_all = arch.seeded_weights(uncut, 11)
    layer_w, embedded = w_all["layers"][0], w_all["embed"][x]
    bias = None if arch.rule == "aux_loss" else jnp.asarray(arch.bias_of(uncut, *STEP_BIAS[name])[0])
    share_of = lambda first, last: dict(layer_w, moe={k: (v[first:last] if k in ("w1", "w3", "w2") else v)
                                                      for k, v in layer_w["moe"].items()})

    def reference_layer(model, weights):
        rest = (weights,) if bias is None else (weights, bias)
        return jnp.stack([R.layer(model, 0, IDENTITY, *rest, jnp.asarray(e))[0] for e in embedded])

    def share(cfg, weights):
        layer = lambda p, e: M._layer(cfg, 0, jnp.float32, p, bias, e)[0]
        return (jax.jit(layer) if name in ("qwen3_next", "nemotron_h", "keye_vl2") else layer)(weights, jnp.asarray(embedded))

    with HIGHEST:
        whole = reference_layer(uncut, layer_w)
        # operator, residual and what is shared, no routed expert: what every share computes alike
        alike = reference_layer({**uncut, "held_experts": [0, 0]}, share_of(0, 0))
        total = alike
        for first in range(0, experts, step):
            part = share(arch.config_of({**m, "held_experts": [first, first + step]}), share_of(first, first + step)) - alike
            assert float(jnp.abs(part).max()) > 0
            total = total + part
        np.testing.assert_allclose(total, whole, atol=arch.tolerance("shares"))
        if name != "lfm2_moe":
            assert float(jnp.abs(whole - alike).max()) > 1e-3, "the routed experts are part of the layer"
        if name == "deepseek_v2":
            no_shared = {**layer_w, "moe": {**layer_w["moe"], "shared": jax.tree_util.tree_map(jnp.zeros_like,
                                                                                               layer_w["moe"]["shared"])}}
            assert float(jnp.abs(whole - reference_layer(uncut, no_shared)).max()) > 1e-3, \
                "the shared experts are part of the layer"
        if name in ("qwen3_next", "laguna", "nemotron_h"):
            without_shared = reference_layer({**uncut, "held_experts": [0, 0], "shared_expert": False}, share_of(0, 0))
            assert float(jnp.abs(alike - without_shared).max()) > 1e-3, "and so is the shared expert, once"
        if name == "laguna":
            unscaled = reference_layer({**uncut, "moe_routed_scaling_factor": 1.0}, layer_w)
            np.testing.assert_allclose(whole - alike, 2.5 * (unscaled - alike), atol=3e-5)  # the factor is on the routed sum alone
        if name == "nemotron_h":
            unscaled = reference_layer({**uncut, "routed_scaling_factor": 1.0}, layer_w)
            np.testing.assert_allclose(whole - alike, 5.0 * (unscaled - alike), atol=1e-4)  # on the routed sum alone
            flat = {**layer_w, "moe": {**layer_w["moe"], "latent_out": jnp.zeros_like(layer_w["moe"]["latent_out"])}}
            np.testing.assert_allclose(reference_layer(uncut, flat), alike, atol=1e-6)  # the routed sum passes W_up, once
