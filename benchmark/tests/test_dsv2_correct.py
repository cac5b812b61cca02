"""The comparison that decides ``correct`` for the ``deepseek_v2`` family can fail
(CPU, rehearsal sizes).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_dsv2_correct.py -q -p no:cacheprovider

One process runs one family (``run.py::load_family``), so every case here runs
this file as a script in a process of its own, which prints the result object
with its checks on its last line (``tests/test_benchmark_dsv2_correct.py``
collects the cases into tier-1):

- a sound run of the harness (the look for a chip skipped) is ``correct``;
- the control -- the reference computed in fp8, put in the program's place --
  fails at least one limit that the sound program passes (the limits are the
  published widths'; at the rehearsal's the float32 program reads ~1e-5);
- the timed path broken underneath gives ``correct: false`` (``FAULTS``, each
  with the check that catches it at these sizes; ``dsv2_readings.py --faults``
  plants the same at the published widths on the chip): rope applied to the
  nope part of q and k too; ``k_pe`` taken per head from ``W_kvb``'s output
  instead of the one shared head; ``m^2`` left out of the softmax scale; the
  router's weights divided by the sum of the chosen six; the shared experts
  left out; the balance term's gradient dropped; an expert outside the share
  added to the layer's sum; a learning rate a fifth too high; a train step that
  returns its state unchanged; a fitness that depends on who was scored before.
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
CELL = "deepseek_v2_lite_ep8.popeval"

#: fault -> the check that catches it at the rehearsal's sizes.
FAULTS = {
    "rope_on_the_nope_part_too": "nll_gap",
    "k_pe_per_head_from_w_kvb": "nll_gap",
    "m_squared_left_out_of_the_scale": "nll_gap",
    "weights_normalised_over_the_chosen": "nll_gap",
    "the_shared_experts_left_out": "nll_gap",
    "balance_gradient_dropped": "moment_gap.router",
    "an_expert_outside_the_share": "nll_gap",
    "learning_rate_a_fifth_high": "dnorm_gap",
    "step_returns_its_state": "dparam_gap.experts",
    "fitness_depends_on_who_came_before": "order_diff",
}


# -- the script: one case in a process of its own -------------------------------------------------------


def plant(fault: str) -> None:
    """Break the timed path underneath, by replacing one function of
    ``gentun_tpu.models.lfm2_moe`` (before its programs are built, or after
    ``_programs.cache_clear()``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gentun_tpu.models import lfm2_moe as M

    def around_the_core(change):
        """``change(q, k, scale, cfg) -> (q, k, scale)`` on what the causal core is handed:
        q (sequences, length, heads, 1, nope + rope) float32, k (sequences, length, heads, nope + rope)."""
        real = M._causal_core

        def core(q, k, v, scale, cfg):
            q, k, scale = change(q, k, scale, cfg)
            return real(q, k, v, scale, cfg)

        M._causal_core = core

    if fault == "rope_on_the_nope_part_too":
        def change(q, k, scale, cfg):
            nope = cfg.qk_nope_head_dim
            turn = lambda a: M._rope(a.astype(jnp.float32), cfg.rope_theta, cfg.yarn).astype(a.dtype)
            q = jnp.concatenate([turn(q[..., 0, :nope])[..., None, :], q[..., nope:]], axis=-1)
            return q, jnp.concatenate([turn(k[..., :nope]), k[..., nope:]], axis=-1), scale

        around_the_core(change)
    elif fault == "k_pe_per_head_from_w_kvb":
        def change(q, k, scale, cfg):  # each head's rope key from its own up-projected columns, not the shared head
            nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            own = M._rope(k[..., :rope].astype(jnp.float32), cfg.rope_theta, cfg.yarn).astype(k.dtype)
            return q, jnp.concatenate([k[..., :nope], own], axis=-1), scale

        around_the_core(change)
    elif fault == "m_squared_left_out_of_the_scale":
        around_the_core(lambda q, k, scale, cfg: (q, k, (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5))
    elif fault == "weights_normalised_over_the_chosen":
        real_route = M._route

        def normalised(router, bias, x, cfg):
            chosen, weight, scores = real_route(router, bias, x, cfg)
            return chosen, weight / weight.sum(-1, keepdims=True), scores

        M._route = normalised
    elif fault == "the_shared_experts_left_out":
        real_ffn = M._moe_ffn
        M._moe_ffn = lambda p, *rest, **kw: real_ffn({k: v for k, v in p.items() if k != "shared"}, *rest, **kw)
    elif fault == "balance_gradient_dropped":
        real_term = M._balance_term
        M._balance_term = lambda *a: jax.lax.stop_gradient(real_term(*a))
    elif fault == "an_expert_outside_the_share":
        real_ffn = M._moe_ffn

        def with_a_foreign_expert(p, bias, x, cfg, dtype, **kw):
            out, load, stats = real_ffn(p, bias, x, cfg, dtype, **kw)
            beyond = dataclasses.replace(cfg, held_experts=(cfg.held_experts[1], cfg.held_experts[1] + 1))
            foreign = {"router": p["router"], **{k: p[k][:1] for k in ("w1", "w3", "w2")}}  # expert 0's weights stand in
            return out + real_ffn(foreign, bias, x, beyond, dtype, **kw)[0], load, stats

        M._moe_ffn = with_a_foreign_expert
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

GROUPS = ("experts", "shared", "router", "latent", "dense_ffn", "embedding", "head", "norms")


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
