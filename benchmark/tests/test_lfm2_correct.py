"""The comparison that decides ``correct`` for the ``lfm2_moe`` family can fail
(CPU, rehearsal sizes).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

One process runs one family (``run.py::load_family``), and
``test_correct.py`` beside this file has loaded the Genetic-CNN's; so every
case here runs this file as a script in a process of its own, which prints
the result object with its checks on its last line:

- a sound run of the harness (the look for a chip skipped) is ``correct``;
- the control -- the reference computed in fp8, put in the program's place --
  fails at least one limit that the sound program passes (the limits are the
  published widths'; at the rehearsal's the float32 program reads ~1e-5);
- the timed path broken underneath gives ``correct: false``, each by the check
  that is there for it: a train step that returns its state unchanged, a
  router whose top-k is taken from the scores without the bias (at these
  sizes the second step's diverged update shows it; ``load_gap``'s limit is
  the published widths' and passes it), an expert outside the share added to
  the layer's sum, a learning rate a fifth too high (the size of the update,
  which sign flips leave alone), and a fitness that depends on who was scored
  before (a leaked donated state).
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
CELL = "lfm2_24b_a2b_ep8.popeval"


# -- the script: one case in a process of its own -------------------------------------------------------


def _plant(fault: str) -> None:
    import jax.numpy as jnp
    import numpy as np

    from gentun_tpu.models import lfm2_moe as M

    if fault == "step_returns_its_state":
        real = M._programs.__wrapped__

        def broken(cfg):
            programs = real(cfg)
            held = jnp.zeros((len(cfg.moe_layers), cfg.n_held), jnp.int32)
            return programs._replace(train_step=lambda state, *rest: (state, jnp.float32(4.0), held))

        M._programs = broken
    elif fault == "top_k_without_the_bias":
        real_route = M._route
        M._route = lambda router, bias, x, cfg: real_route(router, jnp.zeros_like(bias), x, cfg)
    elif fault == "an_expert_outside_the_share":
        real_ffn = M._moe_ffn

        def with_a_foreign_expert(p, bias, x, cfg, dtype, *args, **kw):
            out, load, dropped = real_ffn(p, bias, x, cfg, dtype, *args, **kw)
            beyond = dataclasses.replace(cfg, held_experts=(cfg.held_experts[1], cfg.held_experts[1] + 1))
            foreign = {k: (v if k == "router" else v[:1]) for k, v in p.items()}  # expert 0's weights stand in
            return out + real_ffn(foreign, bias, x, beyond, dtype, *args, **kw)[0], load, dropped

        M._moe_ffn = with_a_foreign_expert
    elif fault == "learning_rate_a_fifth_high":
        real = M._programs.__wrapped__

        def broken(cfg):
            programs = real(cfg)
            faster = jnp.zeros(len(M.GENE_NAMES), jnp.float32).at[0].set(np.log10(1.2))
            return programs._replace(train_step=lambda state, x, y, rows, genes, step: programs.train_step(
                state, x, y, rows, genes + faster, step))

        M._programs = broken
    elif fault == "fitness_depends_on_who_came_before":
        real_cv = M.Lfm2MoeModel.cross_validate_population.__func__

        def leaking(cls, x, y, genomes, **config):
            out = np.asarray(real_cv(cls, x, y, genomes, **config), np.float64)
            return out + 1e-3 * np.arange(len(out))  # what a state carried over from the last individual would do

        M.Lfm2MoeModel.cross_validate_population = classmethod(leaking)
    elif fault:
        raise SystemExit(f"unknown fault {fault!r}")


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
    _plant("" if case == "sound" else case)
    result = harness.run(argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0, rehearsal=True))
    print(json.dumps({"correct": result["correct"], "failed": result["failed"], "attempted": result["attempted"],
                      "checks": {c["name"]: c["ok"] for c in result["checks"]}}))


if __name__ == "__main__":
    _script(sys.argv[1], int(sys.argv[2]))
    sys.exit(0)


# -- the tests ----------------------------------------------------------------------------------------

import pytest  # noqa: E402


def case(name: str, seed: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "GENTUN_TPU_CACHE_DIR": "off", "PYTHONPATH": ROOT}
    ran = subprocess.run([sys.executable, os.path.abspath(__file__), name, str(seed)], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert ran.returncode == 0, ran.stdout[-3000:] + ran.stderr[-3000:]
    return json.loads(ran.stdout.splitlines()[-1])


def test_a_sound_run_is_correct():
    result = case("sound", 2**31 + 41)
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0, result
    groups = ("experts", "router", "operators", "dense_ffn", "embedding", "norms")
    assert set(result["checks"]) == {"units_in_window", "loss_in_range", "loss_mean_ceiling", "order_diff",
                                     "compiles_in_window", "nll_gap", "loss_gap", "dnorm_gap", "load_gap",
                                     "dropped_assignments", *(f"dparam_gap.{g}" for g in groups),
                                     *(f"moment_gap.{g}" for g in groups)}


def test_the_fp8_control_fails_a_limit_that_the_program_passes():
    """Same weights, bias and batches; the reference in fp8 in the program's place."""
    for seed in (31, 32):
        got = case("control", seed)
        limits = got["limits"]
        assert all(got["sound"][k] <= limits[k] for k in got["sound"]), got
        assert any(got["control"][k] > limits[k] for k in got["control"]), got


@pytest.mark.parametrize("fault,caught_by", [
    ("step_returns_its_state", "dparam_gap.experts"),
    ("top_k_without_the_bias", "moment_gap.router"),
    ("an_expert_outside_the_share", "nll_gap"),
    ("learning_rate_a_fifth_high", "dnorm_gap"),
    ("fitness_depends_on_who_came_before", "order_diff"),
])
def test_a_broken_timed_path_is_not_correct(fault, caught_by):
    result = case(fault, 22)
    assert not result["correct"], result
    assert not result["checks"][caught_by], result
