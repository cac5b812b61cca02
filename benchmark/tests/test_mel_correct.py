"""The comparison that decides ``correct`` for the ``mellum`` family can fail
(CPU, rehearsal sizes).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mel_correct.py -q -p no:cacheprovider

One process runs one family (``run.py::load_family``), so every case here runs
this file as a script in a process of its own, which prints the result object
with its checks on its last line (``tests/test_benchmark_mel_correct.py`` and
``tests/test_benchmark_mel_faults.py`` collect the cases into tier-1):

- a sound run of the harness (the look for a chip skipped) is ``correct``;
- the control -- the reference computed in fp8, put in the program's place --
  fails at least one limit that the sound program passes (the limits are the
  published widths'; at the rehearsal's the float32 program reads ~1e-5);
- the timed path broken underneath gives ``correct: false`` (``FAULTS``, each
  with the check that catches it at these sizes; ``mel_readings.py --faults``
  plants the same at the published widths on the chip): the window one key
  short and one key long; the causal mask on a windowed layer; the window on a
  full layer; ``attention_factor`` left out of cos and sin; YaRN's frequencies
  and amplitude on the windowed layers too; the router's weights not normalised
  over the chosen eight; an expert outside the share added to the layer's sum;
  a learning rate a fifth too high; a train step that leaves out half of its
  batch (the first half's sequences stand in for the second's: the gradient of
  half the tokens); a train step that returns its state unchanged; a fitness that depends on who was scored before.
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
CELL = "mellum2_12b_a2p5b_ep8.popeval"

#: fault -> the check that catches it at the rehearsal's sizes.
FAULTS = {
    "window_one_key_short": "nll_gap",
    "window_one_key_long": "nll_gap",
    "causal_mask_on_a_windowed_layer": "nll_gap",
    "window_on_a_full_layer": "nll_gap",
    "attention_factor_left_out": "nll_gap",
    "yarn_on_the_windowed_layers_too": "nll_gap",
    "weights_not_normalised_over_the_chosen": "nll_gap",
    "an_expert_outside_the_share": "nll_gap",
    "learning_rate_a_fifth_high": "dnorm_gap",
    "half_of_the_batch_left_out": "dparam_gap.experts",
    "step_returns_its_state": "dparam_gap.experts",
    "fitness_depends_on_who_came_before": "order_diff",
}
#: Those of them that are planted at the published widths on the chip (``mel_readings.py --faults all``).
PUBLISHED_FAULTS = tuple(FAULTS)[:10]


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
    window_of, rope_of = config.window_of, config.rope_of
    if fault in ("window_one_key_short", "window_one_key_long"):
        by = -1 if fault.endswith("short") else 1
        put(config, "window_of", lambda self, kind: None if window_of(self, kind) is None else window_of(self, kind) + by)
    elif fault == "causal_mask_on_a_windowed_layer":
        put(config, "window_of", lambda self, kind: None)
    elif fault == "window_on_a_full_layer":
        put(config, "window_of", lambda self, kind: self.sliding_window)
    elif fault == "attention_factor_left_out":
        put(M, "yarn_amplitude", lambda scaling: 1.0)
    elif fault == "yarn_on_the_windowed_layers_too":
        put(config, "rope_of", lambda self, kind: rope_of(self, "full_attention"))
    elif fault == "weights_not_normalised_over_the_chosen":
        real_route = M._route

        def as_they_are(router, bias, x, cfg):
            chosen, _, scores = real_route(router, bias, x, cfg)
            return chosen, jnp.take_along_axis(scores, chosen, axis=-1), scores

        put(M, "_route", as_they_are)
    elif fault == "an_expert_outside_the_share":
        real_ffn = M._moe_ffn

        def with_a_foreign_expert(p, bias, x, cfg, dtype, **kw):
            out, load, stats = real_ffn(p, bias, x, cfg, dtype, **kw)
            beyond = dataclasses.replace(cfg, held_experts=(cfg.held_experts[1], cfg.held_experts[1] + 1))
            foreign = {"router": p["router"], **{k: p[k][:1] for k in ("w1", "w3", "w2")}}  # expert 0's weights stand in
            return out + real_ffn(foreign, bias, x, beyond, dtype, **kw)[0], load, stats

        put(M, "_moe_ffn", with_a_foreign_expert)
    elif fault in ("learning_rate_a_fifth_high", "half_of_the_batch_left_out", "step_returns_its_state"):
        real = M._programs  # the lru-cached builder: a step already compiled is wrapped, not built again

        def broken(cfg):
            programs = real(cfg)
            if fault == "step_returns_its_state":
                held = jnp.zeros((len(cfg.moe_layers), cfg.n_held), jnp.int32)
                return programs._replace(train_step=lambda state, *rest: (state, jnp.float32(4.0), held))
            if fault == "half_of_the_batch_left_out":
                half = cfg.batch_sequences // 2
                return programs._replace(train_step=lambda state, x, y, rows, genes, step: programs.train_step(
                    state, x, y, jnp.asarray(rows).at[:, half:].set(jnp.asarray(rows)[:, :half]), genes, step))
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


def _script(case: str, seed: int) -> None:
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("GENTUN_TPU_CACHE_DIR", "off")
    import run as harness

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

GROUPS = ("experts", "router", "attention_window", "attention_full", "embedding", "head", "norms")


def case(name: str, seed: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "GENTUN_TPU_CACHE_DIR": "off", "PYTHONPATH": ROOT}
    ran = subprocess.run([sys.executable, os.path.abspath(__file__), name, str(seed)], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert ran.returncode == 0, ran.stdout[-3000:] + ran.stderr[-3000:]
    return json.loads(ran.stdout.splitlines()[-1])


def test_a_sound_run_is_correct():
    result = case("sound", 2**31 + 41)
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0, result
    assert set(result["checks"]) == {"units_in_window", "loss_finite", "loss_mean_ceiling", "order_diff",
                                     "compiles_in_window", "nll_gap", "loss_gap", "aux_gap", "dnorm_gap", "load_gap",
                                     "dropped_assignments", *(f"dparam_gap.{g}" for g in GROUPS),
                                     *(f"moment_gap.{g}" for g in GROUPS)}


@pytest.mark.parametrize("seed", [31, 32])
def test_the_fp8_control_fails_a_limit_that_the_program_passes(seed):
    """Same weights and batches; the reference in fp8 in the program's place."""
    got = case("control", seed)
    limits = got["limits"]
    assert all(got["sound"][k] <= limits[k] for k in got["sound"]), got
    assert any(got["control"][k] > limits[k] for k in got["control"]), got


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault):
    result = case(fault, 22)
    assert not result["correct"], result
    assert not result["checks"][FAULTS[fault]], result
