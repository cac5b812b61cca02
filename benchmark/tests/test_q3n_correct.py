"""The comparison that decides ``correct`` for the ``qwen3_next`` family can fail
(CPU, rehearsal sizes).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_q3n_correct.py -q -p no:cacheprovider

One process runs one family (``run.py::load_family``), so every case here runs
this file as a script in a process of its own, which prints the result object
with its checks on its last line (``tests/test_benchmark_q3n_correct.py`` and
``tests/test_benchmark_q3n_faults.py`` collect the cases into tier-1):

- a sound run of the harness (the look for a chip skipped) is ``correct``;
- the control -- the reference computed in fp8, put in the program's place --
  fails at least one limit that the sound program passes (the limits are the
  published widths'; at the rehearsal's the float32 program reads ~1e-5);
- the timed path broken underneath gives ``correct: false`` (``FAULTS``, each
  with the check that catches it at these sizes; ``q3n_readings.py --faults``
  plants the same at the published widths on the chip): the delta rule's decay
  left out (``g = 0``); its write strength left out (``beta = 1``); the l2 norm
  of k left out; the state reset at every chunk boundary; the convolution one
  tap short (its oldest tap zero); rope on the whole head; the attention's
  output gate left out; the shared expert's gate left out; an expert outside the
  share added to the layer's sum; the router's weights not normalised over the
  chosen ten; a learning rate a fifth too high; a train step that returns its
  state unchanged; a fitness that depends on who was scored before.
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
CELL = "qwen3_next_80b_a3b_ep16.popeval"

#: fault -> the check that catches it at the rehearsal's sizes.
FAULTS = {
    "decay_left_out": "nll_gap",
    "beta_left_out": "nll_gap",
    "l2_norm_of_k_left_out": "nll_gap",
    "state_reset_at_every_chunk": "nll_gap",
    "convolution_one_tap_short": "nll_gap",
    "rope_on_the_whole_head": "nll_gap",
    "output_gate_left_out": "nll_gap",
    "shared_gate_left_out": "nll_gap",
    "an_expert_outside_the_share": "nll_gap",
    "weights_not_normalised_over_the_chosen": "nll_gap",
    "learning_rate_a_fifth_high": "dnorm_gap",
    "step_returns_its_state": "dparam_gap.experts",
    "fitness_depends_on_who_came_before": "order_diff",
}
#: Those of them that are planted at the published widths on the chip (``q3n_readings.py --faults all``).
PUBLISHED_FAULTS = tuple(FAULTS)[:9]


# -- the script: one case in a process of its own -------------------------------------------------------


def plant(fault: str):
    """Break the timed path underneath, by replacing one function of
    ``gentun_tpu.models.lfm2_moe`` or one property of its configuration (before
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

    config, core = M.Lfm2MoeConfig, M._delta_core
    if fault == "decay_left_out":
        put(M, "_delta_core", lambda q, k, v, g, beta, chunk: core(q, k, v, jnp.zeros_like(g), beta, chunk))
    elif fault == "beta_left_out":
        put(M, "_delta_core", lambda q, k, v, g, beta, chunk: core(q, k, v, g, jnp.ones_like(beta), chunk))
    elif fault == "l2_norm_of_k_left_out":
        real_unit, calls = M._unit_rows, []

        def q_only(a):  # a layer norms q, then k
            calls.append(0)
            return real_unit(a) if len(calls) % 2 else a

        put(M, "_unit_rows", q_only)
    elif fault == "state_reset_at_every_chunk":
        def every_chunk_from_an_empty_state(q, k, v, g, beta, chunk):  # each chunk a sequence of its own
            s, length = q.shape[:2]
            pad = -length % chunk
            split = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)).reshape((-1, chunk) + a.shape[2:])
            out = core(*map(split, (q, k, v, g, beta)), chunk)
            return out.reshape((s, length + pad) + out.shape[2:])[:, :length]

        put(M, "_delta_core", every_chunk_from_an_empty_state)
    elif fault == "convolution_one_tap_short":
        real_mixer = M._linear_attention
        put(M, "_linear_attention", lambda p, x, cfg, dtype: real_mixer(
            {**p, "kernel": p["kernel"].at[:, 0].set(0.0)}, x, cfg, dtype))
    elif fault == "rope_on_the_whole_head":
        put(config, "rotary_dim", property(lambda self: self.head_dim))
    elif fault == "output_gate_left_out":
        real_attention = M._attention

        def ungated(p, x, cfg, dtype, kind="full_attention"):
            h, nh, hd = p["q"].shape[0], cfg.num_attention_heads, cfg.head_dim
            queries = p["q"].reshape(h, nh, 2 * hd)[:, :, :hd].reshape(h, nh * hd)
            return real_attention({**p, "q": queries}, x, dataclasses.replace(cfg, attn_output_gate=False), dtype, kind)

        put(M, "_attention", ungated)
    elif fault == "shared_gate_left_out":
        gated = M._moe_ffn
        put(M, "_moe_ffn", lambda p, *a, **kw: gated({k: v for k, v in p.items() if k != "shared_gate"}, *a, **kw))
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

GROUPS = ("experts", "shared", "router", "linear_attention", "attention_full", "embedding", "head", "norms")


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
