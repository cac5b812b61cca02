"""The routed family's architectures where they meet the rest of the system, one test a property over every
architecture that has it (``routed_family.ARCHS``): the expert layer's row buffer at each architecture's routing,
refusals before anything compiles, the species, the scope rules of each benchmark family, and the readers of the
per-layer metrics each cell lists in the manifest.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

import gentun_tpu
import routed_family as F
import routed_ladder
from gentun_tpu import GeneticAlgorithm, Population
from gentun_tpu.models import lfm2_moe as M
from routed_family import ARCHS, LAGUNA_ROPE as L_ROPE

# -- the row buffer's ladder at each architecture's routing --------------------------------------------------------------


def _ladder_cases():
    return [pytest.param(name, count, rung, dtype, tol, id=f"{name}-{count}-{rung}-{dtype}")
            for name, (_, heights, _, dtypes) in F.LADDERS.items()
            for dtype, tol in dtypes for count, rung in routed_ladder.counts_at_the_rungs(heights) + (
                # every token chooses all the held experts: the worst case, min(top-k, held) x tokens, to its last row
                [(heights[-1], len(heights) - 1)] if name == "nemotron_h" else [])]


@pytest.mark.parametrize("name,count,rung,dtype,tol", _ladder_cases())
def test_the_narrow_and_the_wide_row_buffer_give_the_same_layer(name, count, rung, dtype, tol):
    """Rows that fill a rung of the ladder to its last row, and one row more (the next rung engages): the branch
    the ``switch`` takes and the worst-case height alone are the same function of the same rows, value and
    gradients; nothing is dropped, and what is shared beside the routed experts rides along."""
    arch = ARCHS[name]
    m, heights, tokens, _ = F.LADDERS[name]
    cfg = arch.config_of(m, tokens=F.long_tokens() if name == "lfm2_moe" else None)
    assert M._row_buffer_heights(cfg, tokens) == heights and (name != "lfm2_moe" or cfg.tokens_per_step == tokens)
    w = arch.seeded_weights(m, 3)["layers"][0]["moe"]
    assert ("shared" in w) == (name in ("deepseek_v2", "nemotron_h")) and (name != "mellum2" or (cfg.norm_topk_prob and cfg.scoring_func == "softmax"))
    if name == "nemotron_h":  # 22 a token and 8 held: a token reaches a held expert once, so 8 rows a token at the worst
        assert heights[-1] == min(cfg.num_experts_per_tok, cfg.n_held) * tokens == 8 * tokens and "latent_in" in w
    bias = jnp.asarray(0.01 * np.random.default_rng(5).normal(size=cfg.num_experts), jnp.float32) if arch.rule == "bias" else None
    routed_ladder.assert_the_ladders_layer_is_the_worst_case_heights(cfg, w, bias, tokens, count, rung, dtype, tol)


READERS = {"lfm2_moe": "lm", "deepseek_v2": "dsv2", "mellum2": "mel", "qwen3_next": "q3n", "nemotron_h": "q3n"}


@pytest.mark.parametrize("name", list(READERS))
def test_the_row_buffer_reader_divides_the_rows_the_heights_ran_by_the_rows_routed_and_the_parent_reads_nothing(name):
    with F.as_run_py_loads(ARCHS[name].family) as load:
        reader = load(f"layer_metrics/{READERS[name]}_row_buffer_rows_per_routed_row")
        routed_ladder.assert_the_reader_divides_the_rows_run_by_the_rows_routed(reader)


# -- every metric a cell lists has a reader --------------------------------------------------------------------------------


def _mellum_metrics(per_layer, cell):
    mine = [m for m in per_layer if m.get("workloads", [None])[0] == cell]
    names = [m["name"] for m in mine]
    assert len(names) == 27 and all(n.startswith("mel_") for n in names), names  # 26 of PR 34, the row buffer's of PR 39
    # since PR 42 a second cell with window and full attention mixed reads all of them but the balance term's
    # and since PR 49 a third, whose keys an indexer chooses, reads all 27 (it has a balance term too)
    assert {m["name"] for m in mine if m["workloads"] == [cell, KEYE_CELL]} == {"mel_aux_loss_mean"}
    assert all(m["workloads"] == [cell, "laguna_xs2_ep8.popeval", KEYE_CELL] for m in mine if m["name"] != "mel_aux_loss_mean")
    return names


def _q3n_metrics(per_layer, cell):
    mine = [m for m in per_layer if m.get("workloads", [None])[0] == cell]
    names = [m["name"] for m in mine]
    assert len(names) == 34 and all(n.startswith("q3n_") for n in names), names
    assert not [m["name"] for m in per_layer if cell in m.get("workloads", []) and not m["name"].startswith("q3n_")]
    # since PR 46 a second cell with a scanning mixer beside full attention reads all of them but the balance term's
    assert {m["name"] for m in mine if m["workloads"] == [cell]} == {"q3n_aux_loss_mean"}
    assert all(m["workloads"] == [cell, "nemotron3_super_120b_a12b_ep64.popeval"] for m in mine if m["name"] != "q3n_aux_loss_mean")
    return names


def _laguna_metrics(per_layer, cell):
    """The cell adds no metric: it is appended to accepted ones."""
    names = [m["name"] for m in per_layer if cell in m.get("workloads", ())]
    assert len(names) == 30 and sum(n.startswith("mel_") for n in names) == 26 and "mel_aux_loss_mean" not in names, names
    assert {"device_stall_s", "stall_between_programs_s", "stall_host_late_s", "host_tick_late_max_ms"} < set(names)
    assert all(m["moves"] == ("setup_s" if m["name"] == "mel_first_call_s" else "individuals_per_hour_per_chip")
               and m["workloads"][-2:] == [cell, KEYE_CELL] for m in per_layer if m["name"] in names)
    return names


def _keye_metrics(per_layer, cell):
    """The cell adds no metric: it is appended, last, to the 27 ``mel_*`` entries and the four stall entries."""
    names = [m["name"] for m in per_layer if cell in m.get("workloads", ())]
    assert len(per_layer) == 128 and len(names) == 31 and sum(n.startswith("mel_") for n in names) == 27, names
    assert {"device_stall_s", "stall_between_programs_s", "stall_host_late_s", "host_tick_late_max_ms", "mel_aux_loss_mean",
            "mel_full_core_roofline_share", "mel_window_core_roofline_share", "mel_train_mfu_executed"} < set(names)
    assert all(m["workloads"][-1] == cell for m in per_layer if m["name"] in names)
    return names


def _nemotron_metrics(per_layer, cell):
    """The cell adds no metric: it is appended to accepted ones (the manifest stays at 128)."""
    names = [m["name"] for m in per_layer if cell in m.get("workloads", ())]
    assert len(per_layer) == 128 and len(names) == 33 and all(n.startswith("q3n_") for n in names), names
    assert "q3n_aux_loss_mean" not in names  # the bias rule has no balance term
    assert {"q3n_delta_core_roofline_share", "q3n_full_core_roofline_share", "q3n_expert_mm_roofline_share",
            "q3n_train_mfu_executed", "q3n_device_idle_share", "q3n_peak_hbm_gb"} < set(names)
    assert all(m["moves"] == ("setup_s" if m["name"] == "q3n_first_call_s" else "individuals_per_hour_per_chip")
               and m["workloads"][-1] == cell for m in per_layer if m["name"] in names)
    return names


KEYE_CELL = "keye_vl2_30b_a3b_ep8.popeval"
#: architecture: (its cell, its configuration file, the metrics the manifest gives the cell)
CELLS = {"keye_vl2": (KEYE_CELL, "keye_vl2_30b_a3b_ep8", _keye_metrics),
         "mellum2": ("mellum2_12b_a2p5b_ep8.popeval", "mellum2_12b_a2p5b_ep8", _mellum_metrics),
         "qwen3_next": ("qwen3_next_80b_a3b_ep16.popeval", "qwen3_next_80b_a3b_ep16", _q3n_metrics),
         "laguna": ("laguna_xs2_ep8.popeval", "laguna_xs2_ep8", _laguna_metrics),
         "nemotron_h": ("nemotron3_super_120b_a12b_ep64.popeval", "nemotron3_super_120b_a12b_ep64", _nemotron_metrics)}


@pytest.mark.parametrize("name", list(CELLS))
def test_every_metric_of_the_cell_has_a_reader_that_reads_nothing_from_an_empty_run(name):
    """A program that lacks the spans (the parent's, on the new cell's readers) makes no reader raise."""
    cell, config_name, metrics_of = CELLS[name]
    names = metrics_of(F.manifest()["per_layer"], cell)
    empty = F.empty_run(F.config_file(config_name), cell)
    with F.as_run_py_loads(ARCHS[name].family) as load:
        for metric in names:
            assert load(f"layer_metrics/{metric}").read(dict(empty)) is None, metric


# -- refusals ----------------------------------------------------------------------------------------------------------------

_FULL = L_ROPE["full_attention"]
REFUSALS = {
    "lfm2_moe": [(dict(held_experts=(6, 9)), "held_experts"), (dict(num_dense_layers=3), "routed layer"),
                 (dict(eval_sequences=3), "held-out"), (dict(vocab_size=32), "held slice"),
                 (dict(layer_types=("conv", "hyena", "conv")), "layer_types")],
    "deepseek_v2": [(dict(layer_types=("latent_attention", "retention", "latent_attention")), "layer_types"),
                    (dict(kv_lora_rank=0), "rank and head sizes"), (dict(v_head_dim=0), "rank and head sizes"),
                    (dict(qk_rope_head_dim=3), "even rope size"),
                    (dict(rope_scaling={"factor": 40, "type": "yarn"}), "rope_scaling needs"),
                    (dict(moe_intermediate_size=0), "shared experts of width 0"), (dict(scoring_func="tanh"), "scoring_func"),
                    (dict(balance_rule="none"), "balance_rule")],
    "mellum2": [(dict(sliding_window=0), "sliding_window"),
                (dict(rope_parameters={"full_attention": {"rope_type": "yarn", "rope_theta": 5e5}}), "yarn needs"),
                (dict(rope_parameters={"conv": {"rope_theta": 5e5}}), "rope_parameters"),
                (dict(rope_parameters={"full_attention": {"rope_type": "linear", "rope_theta": 5e5}}), "rope_type"),
                (dict(head_dim=15), "head_dim"), (dict(num_key_value_heads=3), "key-value heads")],
    "qwen3_next": [(dict(linear_num_value_heads=3), "linear_attention layer needs"),
                   (dict(linear_key_head_dim=0), "linear_attention layer needs"),
                   (dict(delta_chunk=0), "linear_attention layer needs"),
                   (dict(partial_rotary_factor=0.0), "partial_rotary_factor"),
                   (dict(partial_rotary_factor=0.2), "partial_rotary_factor"), (dict(n_shared_experts=0), "shared_expert_gate"),
                   (dict(layer_types=("linear_attention", "mamba")), "layer_types")],  # the kind's name is mamba2
    # refused by the layer at fault
    "laguna": [(dict(num_attention_heads_per_layer=(4, 5, 4)), r"layer 1 \(sliding_attention\).*5 heads"),
               (dict(num_attention_heads_per_layer=(4, 6)), "num_attention_heads_per_layer"),
               (dict(num_attention_heads_per_layer=(0, 6, 4)), r"layer 0 \(full_attention\)"),
               (dict(rope_parameters={**L_ROPE, "full_attention": {**_FULL, "partial_rotary_factor": 0.45}}),
                r"layer 0 \(full_attention\): rope turns 7 "),
               (dict(rope_parameters={**L_ROPE, "sliding_attention": {**L_ROPE["sliding_attention"], "partial_rotary_factor": 1.5}}),
                r"layer 1 \(sliding_attention\): rope turns 24 "),
               (dict(attn_output_gate=True), "two forms of one gate"), (dict(sliding_window=0), "sliding_window"),
               (dict(layer_ids=(0, 7, 8), num_attention_heads_per_layer=(4, 6, 3)), r"layer 8 \(full_attention\).*3 heads")],
    # a share of the heads is whole groups; the latent layer is the ungated experts'; a block is one half
    "nemotron_h": [(dict(held_mamba_heads=(1, 3)), "whole groups"), (dict(held_mamba_heads=(2, 2)), "whole groups"),
                   (dict(held_mamba_heads=(2, 6)), "whole groups"), (dict(mlp_hidden_act="silu"), "not for a gated expert"),
                   (dict(mlp_hidden_act="gelu"), "mlp_hidden_act"), (dict(positional_encoding="alibi"), "positional_encoding"),
                   (dict(mamba_n_groups=3), "mamba2 layer needs"), (dict(ssm_state_size=0), "mamba2 layer needs"),
                   (dict(mamba_chunk=0), "mamba2 layer needs"), (dict(num_dense_layers=1), "no dense layer"),
                   (dict(layer_types=("mamba2", "state_space", "routed")), "layer_types")],
    # an indexer has its sizes; rope's sections share out the pairs it turns; the layer reports through a routed feed-forward
    "keye_vl2": [(dict(indexer_num_heads=0), "sparse_attention layer needs"), (dict(indexer_head_dim=7), "sparse_attention layer needs"),
                 (dict(sparse_topk=0), "sparse_attention layer needs"), (dict(mrope_section=(2, 3, 2)), "mrope_section"),
                 (dict(mrope_section=(8, 0, 0)), "mrope_section"), (dict(num_dense_layers=1), "no dense layer"),
                 (dict(layer_types=("sparse_attention", "routed")), "no layer that is one half"),
                 (dict(num_key_value_heads=3), "key-value heads"),
                 (dict(layer_types=("sparse_attention", "sparse")), "layer_types")],
}


@pytest.mark.parametrize("name,index", [pytest.param(name, i, id=f"{name}-{'-'.join(bad)}-{i}")
                                        for name, rows in REFUSALS.items() for i, (bad, _) in enumerate(rows)])
def test_a_configuration_that_cannot_run_is_refused_before_anything_compiles(name, index, monkeypatch):
    arch = ARCHS[name]
    bad, why = REFUSALS[name][index]
    monkeypatch.setattr(M, "_programs", lambda cfg: pytest.fail("a program was asked for"))
    x, y = arch.tokens
    with pytest.raises(ValueError, match=why):
        M.Lfm2MoeModel.cross_validate_population(x, y, [F.genome_of(arch).default()], **arch.model_kwargs(**bad))


# -- the species -------------------------------------------------------------------------------------------------------------


def _lfm2_genome(spec):
    assert spec.names == list(M.GENE_NAMES)
    assert spec.default() == dict(log10_lr=-3.5, warmup_frac=0.25, weight_decay=0.1, beta2=0.95, bias_step=0.001)
    for gene, (lo, hi) in zip(spec.genes, [(-4, -2.5), (0, 0.5), (0, 0.2), (0.9, 0.999), (0, 0.01)]):
        assert (gene.minimum, gene.maximum) == (lo, hi)
    assert gentun_tpu.Lfm2MoeIndividual.fitness_backend() == "Lfm2MoeModel"


def _dsv2_genome(spec):
    assert spec.names == list(M.gene_names("aux_loss")) == gentun_tpu.lfm2_moe_genome().names[:4] + ["aux_alpha"]
    assert spec.default() == dict(log10_lr=-3.5, warmup_frac=0.25, weight_decay=0.1, beta2=0.95, aux_alpha=0.001)
    assert (spec.genes[-1].minimum, spec.genes[-1].maximum) == (0.0, 0.01)
    assert M.gene_names("bias") == M.GENE_NAMES
    np.testing.assert_array_equal(M.gene_vector(spec.default()), np.float32([-3.5, 0.25, 0.1, 0.95, 0.001]))


SPECIES = {"lfm2_moe": ("lfm2-moe", _lfm2_genome), "deepseek_v2": ("deepseek-v2", _dsv2_genome)}


@pytest.mark.parametrize("name", list(SPECIES))
def test_genome_individual_population_and_two_generations(name):
    arch = ARCHS[name]
    x, y = arch.tokens
    SPECIES[name][1](F.genome_of(arch))
    individual = getattr(gentun_tpu, arch.species)
    assert individual.model_cls is M.Lfm2MoeModel and individual.uses_jax
    calls = []

    class Counting(M.Lfm2MoeModel):
        @classmethod
        def cross_validate_population(cls, x_train, y_train, genomes, **config):
            calls.append(len(genomes))
            return super().cross_validate_population(x_train, y_train, genomes, **config)

    class Species(individual):
        model_cls = Counting

    pop = Population(Species, x, y, size=3, seed=0, additional_parameters=arch.model_kwargs(seed=1))
    ga = GeneticAlgorithm(pop, seed=0)
    ga.run(2)
    assert calls and sum(calls) >= 3, "Population.evaluate must reach cross_validate_population"
    best = ga.population.get_fittest()
    assert best.get_fitness() < 0 and best.get_fitness() == max(ga.population.get_fitnesses())
    single = individual(x, y, genes=best.get_genes(), additional_parameters=arch.model_kwargs(seed=1))
    assert single.get_fitness() == pytest.approx(best.get_fitness(), abs=0)
    if arch.rule == "aux_loss":  # a recipe of the other rule is refused by name, not trained under a wrong fifth gene
        with pytest.raises(KeyError, match="aux_alpha"):
            M.Lfm2MoeModel.cross_validate_population(x, y, [gentun_tpu.lfm2_moe_genome().default()], **arch.model_kwargs(seed=1))


@pytest.mark.parametrize("name", list(SPECIES))
def test_the_worker_resolves_the_species(name):
    from gentun_tpu.distributed.worker import _species

    cli_name = SPECIES[name][0]
    assert _species(cli_name) is getattr(gentun_tpu, ARCHS[name].species)
    with pytest.raises(SystemExit, match=cli_name):
        _species("no-such-species")


# -- each benchmark family's scope rules place an op by its scopes ------------------------------------------------------------

PLACED = {
    "lfm2_moe": [  # (op name, its class)
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
        ("", "unattributed")],
    "deepseek_v2": [  # (op name, (its class, its part))
        ("jit(lm_train_step)/jvp(layer1)/latent_attention/core/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/"
         "pallas_call", ("latent_core", "core")),
        ("jit(lm_train_step)/transpose(jvp(jvp()))/checkpoint/layer3/latent_attention/core/mul", ("latent_core", "core")),
        ("jit(lm_eval)/layer0/latent_attention/core/checkpoint/sngqk,sknd->sqngd/dot_general", ("latent_core", "core")),
        ("jit(lm_train_step)/jvp(layer0)/latent_attention/down_proj/dot_general", ("latent_proj", "down_proj")),
        ("jit(lm_train_step)/transpose(jvp(layer2))/latent_attention/up_proj/dot_general", ("latent_proj", "up_proj")),
        ("jit(lm_train_step)/checkpoint/rematted_computation/layer2/latent_attention/rope/cos", ("latent_proj", "rope")),
        ("jit(lm_eval)/layer5/latent_attention/out_proj/dot_general", ("latent_proj", "out_proj")),
        ("jit(lm_train_step)/jvp(layer2)/latent_attention/add", ("latent_proj", "other")),
        ("jit(lm_train_step)/jvp(layer2)/moe/shared/dot_general", ("shared_expert", "shared")),
        ("jit(lm_train_step)/transpose(jvp(layer4))/moe/shared/mul", ("shared_expert", "shared")),
        ("jit(lm_train_step)/jvp(layer2)/cond/branch_0_fun/moe/experts/jit(gmm)/pallas_call", ("expert_mm", "experts")),
        ("jit(lm_train_step)/jvp(layer3)/moe/router/reduce_max", ("moe_route", "router")),
        ("jit(lm_train_step)/jvp(layer3)/aux_loss/reduce_sum", ("moe_route", "aux_loss")),
        ("jit(lm_train_step)/transpose(jvp(layer3))/aux_loss/mul", ("moe_route", "aux_loss")),
        ("jit(lm_eval)/layer5/cond/branch_0_fun/moe/dispatch/jit(_take)/gather", ("moe_route", "dispatch")),
        ("jit(lm_train_step)/transpose(jvp(layer0))/dense_ffn/dot_general", ("dense_ffn", "layer0")),
        ("jit(lm_train_step)/jvp(head)/slh,vh->slv/dot_general", ("head_loss", "head")),
        ("jit(lm_train_step)/optimizer/sqrt", ("optimizer", "optimizer")),
        ("jit(lm_train_step)/jvp(layer3)/rsqrt", ("rest", "layer3")),
        ("", ("unattributed", ""))],
    "nemotron_h": [  # the classes carry the accepted q3n readers' names; the latent projections have their own
        ("jit(lm_train_step)/jvp(layer0)/mamba2/core/Nsgrij,Nsgrjp->Nsgrip/dot_general", ("delta_core", "core")),
        ("jit(lm_train_step)/transpose(jvp(layer2))/mamba2/core/while/body/mul", ("delta_core", "core")),
        ("jit(lm_train_step)/checkpoint/rematted_computation/layer4/mamba2/conv/mul", ("delta_conv", "conv")),
        ("jit(lm_train_step)/jvp(layer0)/mamba2/proj/dot_general", ("delta_proj", "proj")),
        ("jit(lm_train_step)/jvp(layer6)/mamba2/gates/softplus", ("delta_proj", "gates")),
        ("jit(lm_eval)/layer9/mamba2/norm_gate/rsqrt", ("delta_proj", "norm_gate")),
        ("jit(lm_train_step)/jvp(layer7)/full_attention/core/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/"
         "pallas_call", ("full_core", "core")),
        ("jit(lm_train_step)/transpose(jvp(layer7))/full_attention/proj/slngd,ngdh->slh/dot_general", ("attention_proj", "proj")),
        ("jit(lm_train_step)/jvp(layer1)/moe/latent_in/dot_general", ("latent_proj", "latent_in")),
        ("jit(lm_train_step)/transpose(jvp(layer3))/moe/latent_out/dot_general", ("latent_proj", "latent_out")),
        ("jit(lm_train_step)/jvp(layer1)/cond/branch_0_fun/moe/experts/jit(gmm)/pallas_call", ("expert_mm", "experts")),
        ("jit(lm_train_step)/jvp(layer5)/moe/shared/dot_general", ("shared_expert", "shared")),
        ("jit(lm_train_step)/jvp(layer5)/moe/router/top_k", ("moe_route", "router")),
        ("jit(lm_eval)/layer8/cond/branch_1_fun/moe/combine/scatter-add", ("moe_route", "combine")),
        ("jit(lm_train_step)/jvp(head)/slh,vh->slv/dot_general", ("head_loss", "head")),
        ("jit(lm_train_step)/bias_update/sign", ("optimizer", "bias_update")),
        ("jit(lm_train_step)/jvp(layer3)/rsqrt", ("rest", "layer3")),
        ("", ("unattributed", ""))],
    "keye_vl2": [  # the classes carry the accepted mel readers' names: full_core the masked core, window_core the indexer
        ("jit(lm_train_step)/jvp(layer0)/sparse_attention/while/body/checkpoint/core/sqngd,sknd->sngqk/dot_general", ("full_core", "core")),
        ("jit(lm_train_step)/transpose(jvp(layer0))/sparse_attention/while/body/rematted_computation/core/exp", ("full_core", "core")),
        ("jit(lm_train_step)/jvp(layer1)/sparse_attention/while/body/indexer_scores/sqjd,skd->sqjk/dot_general", ("window_core", "indexer_scores")),
        ("jit(lm_train_step)/jvp(layer1)/sparse_attention/while/body/select/while/body/reduce_sum", ("window_core", "select")),
        ("jit(lm_train_step)/transpose(jvp(layer2))/sparse_attention/while/body/checkpoint/indexer_loss/log_softmax", ("window_core", "indexer_loss")),
        ("jit(lm_eval)/layer3/sparse_attention/indexer_proj/slh,hjd->sljd/dot_general", ("attention_proj", "indexer_proj")),
        ("jit(lm_train_step)/jvp(layer3)/sparse_attention/rope/mul", ("attention_proj", "rope")),
        ("jit(lm_train_step)/transpose(jvp(layer3))/sparse_attention/proj/slngd,ngdh->slh/dot_general", ("attention_proj", "proj")),
        ("jit(lm_train_step)/jvp(layer3)/sparse_attention/concatenate", ("attention_proj", "other")),
        ("jit(lm_train_step)/jvp(layer1)/cond/branch_0_fun/moe/experts/jit(gmm)/pallas_call", ("expert_mm", "experts")),
        ("jit(lm_train_step)/jvp(layer1)/moe/router/top_k", ("moe_route", "router")),
        ("jit(lm_train_step)/jvp(layer1)/aux_loss/reduce_sum", ("moe_route", "aux_loss")),
        ("jit(lm_train_step)/jvp(head)/slh,vh->slv/dot_general", ("head_loss", "head")),
        ("jit(lm_train_step)/optimizer/sqrt", ("optimizer", "optimizer")),
        ("jit(lm_train_step)/jvp(layer3)/rsqrt", ("rest", "layer3")),
        ("", ("unattributed", ""))],
}


@pytest.mark.parametrize("name,index", [pytest.param(name, i, id=f"{name}-{i}-{op_name.rsplit('/', 1)[-1]}")
                                        for name, rows in PLACED.items() for i, (op_name, _) in enumerate(rows)])
def test_scope_rules_place_an_op_by_its_scopes(name, index):
    rules = ARCHS[name].scope_rules
    op_name, placed = PLACED[name][index]
    got = rules.classify(op_name)
    assert (got[0] if isinstance(placed, str) else got) == placed and got[0] in rules.CLASSES
