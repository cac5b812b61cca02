"""The comparison that decides ``correct`` for the ``laguna`` family can fail
(CPU, rehearsal sizes), and the family's counts are a hand count there.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lag_correct.py -q -p no:cacheprovider

One process runs one family (``run.py::load_family``), so every case here runs
this file as a script in a process of its own, which prints the result object
with its checks on its last line (``tests/test_benchmark_lag_correct.py`` and
``tests/test_benchmark_lag_faults.py`` collect the cases into tier-1):

- a sound run of the harness (the look for a chip skipped) is ``correct``;
- the control -- the reference computed in fp8, put in the program's place --
  fails at least one limit that the sound program passes (the limits are the
  rehearsal's own, float32's; at the published widths they are bfloat16's);
- the timed path broken underneath gives ``correct: false`` (``FAULTS``, each
  with the check that catches it at these sizes; ``lag_readings.py --faults``
  plants the same at the published widths on the chip): the gate a head left
  out; each head gated by its neighbour's gate; the query heads of a sliding
  layer served by the wrong key-value head; rope on the whole head in a full
  layer; YaRN's ``attention_factor`` left out; the window one kernel block (1,024;
  here twice the window) instead of the published one; the routed sum's factor
  left out; the factor on the shared expert too; an expert outside the share
  added to the layer's sum; the bias left out of the choice; the bias stepped
  towards the load; the router's weights not normalised over the chosen; a
  learning rate a fifth too high; a train step that returns its state unchanged;
  a fitness that depends on who was scored before;
- ``flops.py``'s counts at the rehearsal's sizes against a count by hand
  (``counts``): the products a token at each layer's own query heads, the block
  pairs and the visible share of each mask, the cores' FLOPs and bytes, the
  grouped products' bytes with the dense layer taken off the accepted reader's
  count of layers.
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
CELL = "laguna_xs2_ep8.popeval"

#: fault -> the check that catches it at the rehearsal's sizes.
FAULTS = {
    "gate_left_out": "nll_gap",
    "gate_of_the_neighbouring_head": "nll_gap",
    "sliding_heads_served_by_the_wrong_kv_head": "nll_gap",
    "rope_on_the_whole_head_in_a_full_layer": "nll_gap",
    "attention_factor_left_out": "nll_gap",
    "window_one_block_wide": "nll_gap",
    "scaling_factor_left_out": "nll_gap",
    "scaling_factor_on_the_shared_expert_too": "nll_gap",
    "an_expert_outside_the_share": "nll_gap",
    "bias_left_out_of_the_choice": "load_gap",
    "bias_stepped_towards_the_load": "bias_gap",
    "weights_not_normalised_over_the_chosen": "nll_gap",
    "learning_rate_a_fifth_high": "dnorm_gap",
    "step_returns_its_state": "dparam_gap.experts",
    "fitness_depends_on_who_came_before": "order_diff",
}
#: Those of them that are planted at the published widths on the chip (``lag_readings.py --faults all``).
PUBLISHED_FAULTS = tuple(FAULTS)[:9]


# -- the script: one case in a process of its own -------------------------------------------------------


def plant(fault: str):
    """Break the timed path underneath, by replacing one function of
    ``gentun_tpu.models.lfm2_moe`` or one method of its configuration (before
    its programs are built, or after ``_programs.cache_clear()``).  Returns the
    call that puts back what was replaced."""
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

    config = M.Lfm2MoeConfig
    real_attention, real_ffn, real_route, real_core = M._attention, M._moe_ffn, M._route, M._causal_core
    if fault == "gate_left_out":
        put(M, "_attention", lambda p, x, cfg, *a, **kw: real_attention(
            {k: v for k, v in p.items() if k != "gate"}, x, dataclasses.replace(cfg, attn_head_gate=False), *a, **kw))
    elif fault == "gate_of_the_neighbouring_head":
        put(M, "_attention", lambda p, x, *a, **kw: real_attention({**p, "gate": jnp.roll(p["gate"], 1, axis=1)}, x, *a, **kw))
    elif fault == "sliding_heads_served_by_the_wrong_kv_head":
        put(M, "_causal_core", lambda q, k, v, scale, cfg, window=None: real_core(
            q, *((k, v) if window is None else (jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2))), scale, cfg, window))
    elif fault == "rope_on_the_whole_head_in_a_full_layer":
        put(config, "rotary_of", lambda self, kind: self.head_dim)
    elif fault == "attention_factor_left_out":
        put(M, "yarn_amplitude", lambda scaling: 1.0)
    elif fault == "window_one_block_wide":
        window_of = config.window_of
        put(config, "window_of", lambda self, kind: None if window_of(self, kind) is None else 2 * window_of(self, kind))
    elif fault == "scaling_factor_left_out":
        put(M, "_moe_ffn", lambda p, bias, x, cfg, *a, **kw: real_ffn(
            p, bias, x, dataclasses.replace(cfg, routed_scaling_factor=1.0), *a, **kw))
    elif fault == "scaling_factor_on_the_shared_expert_too":
        put(M, "_moe_ffn", lambda p, bias, x, cfg, *a, **kw: real_ffn(
            {**p, "shared": {**p["shared"], "w2": p["shared"]["w2"] * cfg.routed_scaling_factor}}, bias, x, cfg, *a, **kw))
    elif fault == "an_expert_outside_the_share":
        def with_a_foreign_expert(p, bias, x, cfg, dtype, **kw):
            out, load, stats = real_ffn(p, bias, x, cfg, dtype, **kw)
            beyond = dataclasses.replace(cfg, held_experts=(cfg.held_experts[1], cfg.held_experts[1] + 1))
            # expert 0's weights stand in; the shared expert is the share's own and is not added again
            foreign = {"router": p["router"], **{k: p[k][:1] for k in ("w1", "w3", "w2")}}
            return out + real_ffn(foreign, bias, x, beyond, dtype, **kw)[0], load, stats

        put(M, "_moe_ffn", with_a_foreign_expert)
    elif fault == "bias_left_out_of_the_choice":
        put(M, "_route", lambda router, bias, x, cfg: real_route(router, jnp.zeros_like(bias), x, cfg))
    elif fault == "weights_not_normalised_over_the_chosen":
        def as_they_are(router, bias, x, cfg):
            chosen, _, scores = real_route(router, bias, x, cfg)
            return chosen, jnp.take_along_axis(scores, chosen, axis=-1), scores

        put(M, "_route", as_they_are)
    elif fault in ("learning_rate_a_fifth_high", "step_returns_its_state", "bias_stepped_towards_the_load"):
        real = M._programs  # the lru-cached builder: a step already compiled is wrapped, not built again

        def broken(cfg):
            programs = real(cfg)
            if fault == "step_returns_its_state":
                held = jnp.zeros((len(cfg.moe_layers), cfg.n_held), jnp.int32)
                return programs._replace(train_step=lambda state, *rest: (state, jnp.float32(4.0), held))
            if fault == "bias_stepped_towards_the_load":
                other_way = jnp.ones(len(M.GENE_NAMES), jnp.float32).at[-1].set(-1.0)
                return programs._replace(train_step=lambda state, x, y, rows, genes, step: programs.train_step(
                    state, x, y, rows, genes * other_way, step))
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
    kinds = ("sliding_attention", "full_attention")
    visits = {kind: flops.block_visits(m, kind, length) for kind in kinds}
    return {"model": {k: m[k] for k in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer", "held_experts")},
            "length": length, "linear_flops_per_token": flops.linear_flops_per_token(m), "visits": visits,
            "visible": {kind: flops.visible_elements(m, kind, length) for kind in kinds},
            "heads": {kind: flops.heads_of(m, kind) for kind in kinds},
            "core_flops": {kind: flops.core_flops(m, visits[kind], 3, 2, 1, sum(flops.heads_of(m, kind))) for kind in kinds},
            "core_bytes": {kind: flops.core_bytes(m, 3, length, 2, 1, flops.heads_of(m, kind)) for kind in kinds},
            "expert_mm_flops": flops.expert_mm_flops(m, 1000, 4), "expert_mm_bytes": flops.expert_mm_bytes(m, 1000, 4, 9),
            "train_flops": flops.train_flops(m, 384, 1000, length)}


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

GROUPS = ("experts", "shared", "dense", "router", "attention_full", "attention_sliding", "gates", "embedding", "head",
          "norms")


def case(name: str, seed: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "GENTUN_TPU_CACHE_DIR": "off", "PYTHONPATH": ROOT}
    ran = subprocess.run([sys.executable, os.path.abspath(__file__), name, str(seed)], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert ran.returncode == 0, ran.stdout[-3000:] + ran.stderr[-3000:]
    return json.loads(ran.stdout.splitlines()[-1])


def test_a_sound_run_is_correct():
    result = case("sound", 2**31 + 41)
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0, result
    assert set(result["checks"]) == {"units_in_window", "loss_in_range", "loss_mean_ceiling", "order_diff",
                                     "compiles_in_window", "nll_gap", "loss_gap", "dnorm_gap", "load_gap", "bias_gap",
                                     "dropped_assignments", *(f"dparam_gap.{g}" for g in GROUPS),
                                     *(f"moment_gap.{g}" for g in GROUPS)}


@pytest.mark.parametrize("seed", [31, 32])
def test_the_fp8_control_fails_a_limit_that_the_program_passes(seed):
    """Same weights, bias and batches; the reference in fp8 in the program's place."""
    got = case("control", seed)
    limits = got["limits"]
    assert all(got["sound"][k] <= limits[k] for k in got["sound"]), got
    assert any(got["control"][k] > limits[k] for k in got["control"]), got


def test_the_counts_are_a_hand_count_at_the_rehearsals_sizes():
    """Hidden 64, heads of 16 columns, 2 key-value heads; layer 0 dense (96) under full attention at 4 query heads,
    layer 1 sparse under a window of 24 at 6, layer 4 sparse under full attention at 4; 16 experts of width 48, 2
    held, a shared expert of 48; 128 ids; 64 positions, so one block pair a head whatever the mask."""
    got = case("counts", 0)
    assert got["model"] == {"layer_types": ["full_attention", "sliding_attention", "full_attention"],
                            "mlp_layer_types": ["dense", "sparse", "sparse"], "num_attention_heads_per_layer": [4, 6, 4],
                            "held_experts": [0, 2]} and got["length"] == 64
    # q and o at the layer's own heads, k and v at 2, the head gates; the dense SwiGLU; two routers and shared experts
    attention = (64 * 16 * (2 * 4 + 4) + 64 * 4) + (64 * 16 * (2 * 6 + 4) + 64 * 6) + (64 * 16 * (2 * 4 + 4) + 64 * 4)
    assert attention == 41_856
    linear = 2 * (attention + 3 * 64 * 96 + 2 * (64 * 16 + 3 * 64 * 48))
    assert got["linear_flops_per_token"] == linear == 161_536
    one_pair = {"pairs": 1, "elements": 64 * 64, "pairs_bwd": 1, "elements_bwd": 64 * 64}
    assert got["visits"] == {"sliding_attention": one_pair, "full_attention": one_pair}
    assert got["visible"] == {"sliding_attention": 24 * 25 // 2 + 40 * 24, "full_attention": 64 * 65 // 2}
    assert got["heads"] == {"sliding_attention": [6], "full_attention": [4, 4]}
    # a head and sequence: the forward kernel twice (2 products), the backward once (5 products), 2 FLOPs a multiply-add
    per_head = 64 * 64 * 16 * (2 * 2 * 2 + 1 * 2 * 5)
    assert got["core_flops"] == {"sliding_attention": 3 * 6 * per_head, "full_attention": 3 * 8 * per_head}
    forward = lambda nh: nh * (2 * 2 * 16 + 4) + 2 * 2 * 2 * 16  # q read, o written, lse; k, v read
    backward = lambda nh: nh * (2 * 4 * 16 + 4) + 2 * 2 * 4 * 16  # q, o, do read, dq written, lse; k, v read, dk, dv written
    assert got["core_bytes"] == {"sliding_attention": 3 * 64 * (2 * forward(6) + backward(6)),
                                 "full_attention": 3 * 64 * 2 * (2 * forward(4) + backward(4))}
    assert got["expert_mm_flops"] == 4 * 1000 * 3 * 2 * 64 * 48
    # 9 layer-steps as the accepted reader counts them (3 layers x 3 steps): 6 of them routed; 2 held experts' 3 matrices
    assert got["expert_mm_bytes"] == 4 * (2 * (2 * (64 + 48) + (48 + 64)) * 1000 + 6 * 2 * 3 * 64 * 48 * 2)
    cores = (384 / 64) * (6 + 8) * per_head
    assert got["train_flops"] == 384 * (4 * linear + 3 * 2 * 64 * 128) + cores + 4 * 1000 * 3 * 2 * 64 * 48


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
