"""The comparison that decides ``correct`` for the ``keye_vl2`` family can fail
(CPU, rehearsal sizes), and the family's counts are a hand count there.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_kvl_correct.py -q -p no:cacheprovider

One process runs one family (``run.py::load_family``), so every case here runs
this file as a script in a process of its own, which prints the result object
with its checks on its last line (``tests/test_benchmark_kvl_correct.py`` and
``tests/test_benchmark_kvl_faults.py`` collect the cases into tier-1):

- a sound run of the harness (the look for a chip skipped) is ``correct``;
- the control -- the reference computed in fp8, put in the program's place --
  fails at least one limit that the sound program passes (the limits are the
  rehearsal's own, float32's; at the published widths they are bfloat16's);
- the timed path broken underneath gives ``correct: false`` (``FAULTS``, each
  with the check that catches it at these sizes; ``kvl_readings.py --faults``
  plants the same at the published widths on the chip): the selection ignored
  (dense attention); ``topk`` one short; the relu left out of the indexer's
  score; its weights ``w`` left out; future keys admitted to the ranking; keys
  chosen by whole chunks; a norm on ``kI``; the indexer's rope left out; ``u`` not
  detached where the indexer reads it; the target ``p`` not detached; ``p`` not
  divided by the heads; the KL over all causal keys; the q/k norm left out; an
  expert outside the share added to the layer's sum; and what any routed model
  could have: the router's weights not normalised over the chosen, a learning
  rate a fifth too high, a train step that returns its state unchanged, a
  fitness that depends on who was scored before;
- ``flops.py``'s counts at the rehearsal's sizes against a count by hand
  (``counts``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "keye_vl2_30b_a3b_ep8.popeval"

#: fault -> the check that catches it at the rehearsal's sizes.
FAULTS = {
    "selection_ignored": "selection_gap",
    "topk_one_short": "selection_gap",
    "relu_left_out": "selection_gap",
    "w_left_out": "selection_gap",
    "future_keys_in_the_ranking": "selection_gap",
    "keys_chosen_by_whole_chunks": "selection_gap",
    "a_norm_on_the_indexers_key": "selection_gap",
    "indexer_rope_left_out": "selection_gap",
    "u_not_detached": "dparam_gap.attention",
    "p_not_detached": "dparam_gap.attention",
    "p_not_divided_by_the_heads": "indexer_loss_gap",
    "kl_over_all_causal_keys": "indexer_loss_gap",
    "qk_norm_left_out": "nll_gap",
    "an_expert_outside_the_share": "nll_gap",
    "weights_not_normalised_over_the_chosen": "nll_gap",
    "learning_rate_a_fifth_high": "dnorm_gap",
    "step_returns_its_state": "dparam_gap.experts",
    "fitness_depends_on_who_came_before": "order_diff",
}
#: Those of them that are planted at the published widths on the chip (``kvl_readings.py --faults all``).
PUBLISHED_FAULTS = tuple(FAULTS)[:14]


# -- the script: one case in a process of its own -------------------------------------------------------


def plant(fault: str):
    """Break the timed path underneath, by replacing one function of
    ``gentun_tpu.models.lfm2_moe`` (before its programs are built, or after
    ``_programs.cache_clear()``).  Returns the call that puts back what was
    replaced."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gentun_tpu.models import lfm2_moe as M

    replaced = []

    def put(owner, name, value):
        replaced.append((owner, name, owner.__dict__[name] if isinstance(owner, type) else getattr(owner, name)))
        setattr(owner, name, value)

    def undo():
        for owner, name, value in reversed(replaced):
            setattr(owner, name, value)

    real_ffn, real_route, real_scores, real_selection = M._moe_ffn, M._route, M._indexer_scores, M._sparse_selection
    real_kth, real_operands, real_sparse, real_loss = M._kth_largest, M._indexer_operands, M._sparse_attention, M._indexer_loss
    if fault == "selection_ignored":  # every causal key kept: dense attention
        put(M, "_sparse_selection", lambda q, k, w, top, block: real_selection(q, k, w, q.shape[1], block))
    elif fault == "topk_one_short":
        put(M, "_sparse_selection", lambda q, k, w, top, block: real_selection(q, k, w, top - 1, block))
    elif fault == "relu_left_out":
        def without_relu(q_idx, k_idx, w_idx, first):
            dots = jnp.einsum("sqjd,skd->sqjk", q_idx, k_idx, preferred_element_type=jnp.float32)
            return jnp.where(jnp.isneginf(real_scores(q_idx, k_idx, w_idx, first)), -jnp.inf, jnp.sum(dots * w_idx[..., None], axis=2))

        put(M, "_indexer_scores", without_relu)
    elif fault == "w_left_out":  # every head at the same weight: the scale alone
        put(M, "_indexer_scores", lambda q_idx, k_idx, w_idx, first: real_scores(
            q_idx, k_idx, jnp.full_like(w_idx, (q_idx.shape[2] * q_idx.shape[3]) ** -0.5), first))
    elif fault == "future_keys_in_the_ranking":  # the keys ahead of a query rank with its best: fewer of its own pass
        put(M, "_kth_largest", lambda scores, k: real_kth(
            jnp.where(jnp.isneginf(scores), jnp.max(scores, axis=-1, keepdims=True), scores), k))
    elif fault == "keys_chosen_by_whole_chunks":  # a key scores what the best key of its chunk scores: whole chunks pass
        seen = {}

        def noting_topk(indexer, x, cfg, dtype, positions=None):  # both callers of the scores make their operands first
            seen["chunk"] = max(cfg.sparse_topk // 4, 1)
            return real_operands(indexer, x, cfg, dtype, positions)

        def pooled(q_idx, k_idx, w_idx, first):
            index, chunk = real_scores(q_idx, k_idx, w_idx, first), seen["chunk"]
            best = jnp.max(index.reshape(*index.shape[:2], -1, chunk), axis=-1, keepdims=True)
            best = jnp.broadcast_to(best, (*index.shape[:2], index.shape[2] // chunk, chunk)).reshape(index.shape)
            return jnp.where(jnp.isneginf(index), -jnp.inf, best)

        put(M, "_indexer_operands", noting_topk)
        put(M, "_indexer_scores", pooled)
    elif fault == "a_norm_on_the_indexers_key":
        def normed_key(indexer, x, cfg, dtype, positions=None):
            q_idx, k_idx, w_idx = real_operands(indexer, x, cfg, dtype, positions)
            k32 = k_idx.astype(jnp.float32)
            k32 = (k32 - k32.mean(-1, keepdims=True)) * jax.lax.rsqrt(k32.var(-1, keepdims=True) + 1e-6)
            return q_idx, k32.astype(k_idx.dtype), w_idx

        put(M, "_indexer_operands", normed_key)
    elif fault == "indexer_rope_left_out":  # ``_rope`` turns the indexer's columns alone in this architecture
        put(M, "_rope", lambda x, theta, scaling=None, positions=None: x)
    elif fault == "u_not_detached":
        put(M, "_indexer_reads", lambda x: x)
    elif fault == "p_not_detached":
        put(M, "_heads_share", lambda prob: jnp.mean(prob, axis=(1, 2)))
    elif fault == "p_not_divided_by_the_heads":
        put(M, "_heads_share", lambda prob: jax.lax.stop_gradient(jnp.sum(prob, axis=(1, 2))))
    elif fault == "kl_over_all_causal_keys":  # the scores' softmax over every key up to the query, not over the kept
        put(M, "_indexer_loss", lambda index, share, kept: real_loss(index, share, index > -jnp.inf))
    elif fault == "qk_norm_left_out":
        put(M, "_sparse_attention", lambda p, indexer, x, cfg, *a, **kw: real_sparse(
            p, indexer, x, dataclasses.replace(cfg, qk_norm=False), *a, **kw))
    elif fault == "an_expert_outside_the_share":
        def with_a_foreign_expert(p, bias, x, cfg, dtype, **kw):
            out, load, stats = real_ffn(p, bias, x, cfg, dtype, **kw)
            beyond = dataclasses.replace(cfg, held_experts=(cfg.held_experts[1], cfg.held_experts[1] + 1))
            foreign = {"router": p["router"], **{k: p[k][:1] for k in ("w1", "w3", "w2")}}  # expert 0's weights stand in
            return out + real_ffn(foreign, bias, x, beyond, dtype, **kw)[0], load, stats

        put(M, "_moe_ffn", with_a_foreign_expert)
    elif fault == "weights_not_normalised_over_the_chosen":
        def as_they_are(router, bias, x, cfg):
            chosen, _, scores = real_route(router, bias, x, cfg)
            return chosen, jnp.take_along_axis(scores, chosen, axis=-1), scores

        put(M, "_route", as_they_are)
    elif fault in ("learning_rate_a_fifth_high", "step_returns_its_state"):
        real = M._programs  # the lru-cached builder: a step already compiled is wrapped, not built again

        def broken(cfg):
            programs = real(cfg)
            if fault == "step_returns_its_state":
                held = jnp.zeros((len(cfg.moe_layers), cfg.n_held), jnp.int32)
                return programs._replace(train_step=lambda state, *rest: (state, jnp.float32(4.0), held))
            faster = jnp.zeros(len(M.GENE_NAMES), jnp.float32).at[0].set(np.log10(1.2))
            return programs._replace(train_step=lambda state, x, y, rows, genes, step: programs.train_step(
                state, x, y, rows, genes + faster, step))

        broken.cache_clear = real.cache_clear
        put(M, "_programs", broken)
    elif fault == "fitness_depends_on_who_came_before":
        real_cv = M.Lfm2MoeModel.cross_validate_population.__func__

        def leaking(cls, x, y, genomes, **config):
            out = np.asarray(real_cv(cls, x, y, genomes, **config), np.float64)
            return out + 1e-3 * np.arange(len(out))  # what a state carried over from the last individual would do

        put(M.Lfm2MoeModel, "cross_validate_population", classmethod(leaking))
    elif fault:
        raise SystemExit(f"unknown fault {fault!r}")
    return undo


def _counts() -> dict:
    """``flops.py``'s counts at the rehearsal's sizes (the family loaded as the harness loads it)."""
    import run as harness

    _, _, config, _ = harness.load_cell(CELL, rehearsal=True)
    family = harness.load_family(config["family"])
    import flops

    m = family.model_block(config)
    length = config["data"]["seq_len"]
    return {"model": {k: m[k] for k in ("num_hidden_layers", "topk", "indexer_num_heads", "indexer_head_dim", "held_experts")},
            "length": length, "linear_flops_per_token": flops.linear_flops_per_token(m),
            "causal": flops.causal_elements(length), "chosen": flops.chosen_elements(m, length),
            "blocks": flops.block_elements(length, 32),
            "core_flops": flops.core_flops(m, flops.chosen_elements(m, length), 3, 2, 1),
            "core_bytes": flops.core_bytes(m, 3, length, 2, 1),
            "indexer_flops": flops.indexer_flops(m, flops.causal_elements(length), 3, 2, 1),
            "indexer_bytes": flops.indexer_bytes(m, 3, length, 2, 1),
            "expert_mm_flops": flops.expert_mm_flops(m, 1000, 4), "expert_mm_bytes": flops.expert_mm_bytes(m, 1000, 4, 6),
            "train_flops": flops.train_flops(m, 288, 1000, length, {"full_attention": flops.block_elements(length, 32)})}


def _script(case: str, seed: int) -> None:
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("GENTUN_TPU_CACHE_DIR", "off")
    import run as harness

    if case == "counts":
        print(json.dumps(_counts()))
        return
    if case == "control":
        _, _, config, mix = harness.load_cell(CELL, rehearsal=True)
        family = harness.load_family(config["family"])
        ctx = harness.Ctx(config=config, seed=seed, **family.make_inputs(config, mix, seed, rehearsal=True))
        sound, control = family.after_window(ctx, family.program_side(ctx), "fp8")
        print(json.dumps({"sound": {c["name"]: c["value"] for c in sound}, "control": control,
                          "limits": {c["name"]: c["limit"] for c in sound}}))
        return
    plant("" if case == "sound" else case)
    result = harness.run(argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0, rehearsal=True))
    print(json.dumps({"correct": result["correct"], "failed": result["failed"], "attempted": result["attempted"],
                      "checks": {c["name"]: c["ok"] for c in result["checks"]},
                      "values": {c["name"]: c["value"] for c in result["checks"]}}))


if __name__ == "__main__":
    _script(sys.argv[1], int(sys.argv[2]))
    sys.exit(0)



# -- the tests ----------------------------------------------------------------------------------------

import pytest  # noqa: E402

GROUPS = ("experts", "router", "attention", "indexer", "embedding", "head", "norms")


def case(name: str, seed: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "GENTUN_TPU_CACHE_DIR": "off", "PYTHONPATH": ROOT}
    ran = subprocess.run([sys.executable, os.path.abspath(__file__), name, str(seed)], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert ran.returncode == 0, ran.stdout[-3000:] + ran.stderr[-3000:]
    return json.loads(ran.stdout.splitlines()[-1])


def test_a_sound_run_is_correct():
    result = case("sound", 2**31 + 49)
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0, result
    assert set(result["checks"]) == {"units_in_window", "loss_in_range", "loss_mean_ceiling", "order_diff",
                                     "compiles_in_window", "nll_gap", "loss_gap", "aux_gap", "indexer_loss_gap",
                                     "selection_gap", "selected_pairs", "dnorm_gap", "load_gap", "dropped_assignments",
                                     *(f"dparam_gap.{g}" for g in GROUPS), *(f"moment_gap.{g}" for g in GROUPS)}
    assert result["values"]["selection_gap"] == 0.0 and result["values"]["selected_pairs"] <= 8  # a tie at a score of exactly zero keeps both


@pytest.mark.parametrize("seed", [31, 32])
def test_the_fp8_control_fails_a_limit_that_the_program_passes(seed):
    """Same weights and batches; the reference in fp8 in the program's place."""
    got = case("control", seed)
    limits = got["limits"]
    assert all(got["sound"][k] <= limits[k] for k in got["sound"]), got
    assert any(got["control"][k] > limits[k] for k in got["control"]), got
    assert got["control"]["selection_gap"] > limits["selection_gap"], "fp8 scores choose other keys"


def test_the_counts_are_a_hand_count_at_the_rehearsals_sizes():
    """Hidden 64, 4 query heads over 2 key-value heads of 16 columns, an indexer of 8 heads of 8 that keeps 16 keys;
    2 layers, 8 experts of width 48 with 2 held; 128 ids; 96 positions in blocks of 32."""
    got = case("counts", 0)
    assert got["model"] == {"num_hidden_layers": 2, "topk": 16, "indexer_num_heads": 8, "indexer_head_dim": 8,
                            "held_experts": [0, 2]} and got["length"] == 96
    # q and o at 4 heads, k and v at 2; the indexer's queries, its one key and its weights; the router
    linear = 2 * 2 * (64 * 16 * (2 * 4 + 2 * 2) + 64 * (8 * 8 + 8 + 8) + 64 * 8)
    assert got["linear_flops_per_token"] == linear == 71_680
    assert got["causal"] == 96 * 97 // 2 and got["chosen"] == 16 * 17 // 2 + 80 * 16 == 1_416
    assert got["blocks"] == {"pairs": 3, "elements": 96 * 96}  # one group of three blocks, each against all 96 keys
    # the model's core a head: forward twice (2 products), backward once (5 products), 2 FLOPs a multiply-add
    assert got["core_flops"] == 3 * 2 * 4 * 1_416 * 16 * (2 * 4 + 10)
    forward = 4 * (2 * 2 * 16 + 4) + 2 * 2 * 2 * 16 + 4  # q read, o written, lse; k, v read; the threshold
    backward = 4 * (2 * 4 * 16 + 4) + 2 * 2 * 4 * 16 + 4
    assert got["core_bytes"] == 3 * 2 * 96 * (2 * forward + backward)
    # the indexer: a product of 8 columns a head and causal pair, forward twice, its two backward products once
    assert got["indexer_flops"] == 3 * 2 * (96 * 97 // 2) * 2 * 8 * 8 * (2 + 2)
    index_forward = 2 * (8 * 8 + 8) + 4 * 8 + 4
    assert got["indexer_bytes"] == 3 * 2 * 96 * (2 * index_forward + 2 * index_forward)
    assert got["expert_mm_flops"] == 4 * 1000 * 3 * 2 * 64 * 48
    assert got["expert_mm_bytes"] == 4 * (2 * (2 * (64 + 48) + (48 + 64)) * 1000 + 6 * 2 * 3 * 64 * 48 * 2)
    # executed: the blocks' 9,216 pairs a head; the core's two products twice forward, four backward products;
    # the indexer's product three times forward, two backward products
    executed = 3 * 2 * (4 * 9_216 * 16 * 2 * (2 * 2 + 4) + 9_216 * 2 * 8 * 8 * (3 + 2))
    assert got["train_flops"] == 288 * (4 * linear + 3 * 2 * 64 * 128) + executed + 4 * 1000 * 3 * 2 * 64 * 48


#: The faults that any routed architecture could have; the others are this one's own.
GENERIC_FAULTS = ("weights_not_normalised_over_the_chosen", "learning_rate_a_fifth_high", "step_returns_its_state",
                  "fitness_depends_on_who_came_before")


def _fails_its_check(fault):
    result = case(fault, 22)
    assert not result["correct"], result
    assert not result["checks"][FAULTS[fault]], result


@pytest.mark.parametrize("fault", GENERIC_FAULTS)
def test_a_fault_any_routed_model_could_have_is_not_correct(fault):
    _fails_its_check(fault)


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - set(GENERIC_FAULTS)))
def test_a_broken_timed_path_is_not_correct(fault):
    _fails_its_check(fault)
