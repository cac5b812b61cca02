"""The comparison that decides ``correct`` for the ``nemotron_h`` family can fail
(CPU, rehearsal sizes), and the family's counts are a hand count there.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_nmh_correct.py -q -p no:cacheprovider

One process runs one family (``run.py::load_family``), so every case here runs
this file as a script in a process of its own, which prints the result object
with its checks on its last line (``tests/test_benchmark_nmh_correct.py``,
``tests/test_benchmark_nmh_faults.py`` and ``tests/test_benchmark_nmh_mixer_faults.py``
collect the cases into tier-1):

- a sound run of the harness (the look for a chip skipped) is ``correct``;
- the control -- the reference computed in fp8, put in the program's place --
  fails at least one limit that the sound program passes (the limits are the
  rehearsal's own, float32's; at the published widths they are bfloat16's);
- the timed path broken underneath gives ``correct: false`` (``FAULTS``, each
  with the check that catches it at these sizes; ``nmh_readings.py --faults``
  plants the same at the published widths on the chip): of the Mamba-2 mixer
  (``MIXER_FAULTS``) the state reset at every chunk boundary, the skip ``D x``
  left out, the norm before the gate, the norm over a head's channels in place
  of the group's, ``dt`` without its softplus, the convolution's bias left out;
  of the latent expert layer ``relu2`` as ``relu``, the routed sum's factor left
  out, the factor on the shared expert too, the weights normalised over the held
  chosen experts in place of all the chosen, the shared expert left out, an
  expert outside the share added to the layer's sum; a rotary encoding applied
  in attention; and what any routed model could have: the bias left out of the
  choice; the bias stepped towards the load; the router's weights not normalised
  over the chosen; a learning rate a fifth too high; a train step that returns
  its state unchanged; a fitness that depends on who was scored before;
- ``flops.py``'s counts at the rehearsal's sizes against a count by hand
  (``counts``): the products a token of each block type at the heads held, the
  block pairs of the causal mask, the cores' FLOPs and bytes, the grouped
  products' bytes with the blocks that are not routed taken off the accepted
  reader's count of layers.
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
CELL = "nemotron3_super_120b_a12b_ep64.popeval"

#: The Mamba-2 mixer's faults -> the check that catches each at the rehearsal's sizes.
MIXER_FAULTS = {
    "state_reset_at_every_chunk_boundary": "nll_gap",
    "skip_left_out": "nll_gap",
    "norm_before_the_gate": "nll_gap",
    "norm_over_a_heads_channels": "nll_gap",
    "dt_without_its_softplus": "nll_gap",
    "conv_bias_left_out": "nll_gap",
}
#: Every fault -> the check that catches it at the rehearsal's sizes.
FAULTS = {
    **MIXER_FAULTS,
    "relu2_as_relu": "nll_gap",
    "scaling_factor_left_out": "nll_gap",
    "scaling_factor_on_the_shared_expert_too": "nll_gap",
    "weights_normalised_over_the_held_chosen": "nll_gap",
    "shared_expert_left_out": "nll_gap",
    "an_expert_outside_the_share": "nll_gap",
    "a_rotary_encoding_applied": "nll_gap",
    "bias_left_out_of_the_choice": "load_gap",
    "bias_stepped_towards_the_load": "bias_gap",
    "weights_not_normalised_over_the_chosen": "nll_gap",
    "learning_rate_a_fifth_high": "dnorm_gap",
    "step_returns_its_state": "dparam_gap.experts",
    "fitness_depends_on_who_came_before": "order_diff",
}
#: Those of them that are planted at the published widths on the chip (``nmh_readings.py --faults all``).
PUBLISHED_FAULTS = tuple(FAULTS)[:13]


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

    real_mixer, real_core, real_attention, real_ffn, real_route = (M._state_space, M._state_space_core, M._attention,
                                                                   M._moe_ffn, M._route)
    without = lambda p, name: {**p, name: jnp.zeros_like(p[name])}
    if fault == "state_reset_at_every_chunk_boundary":
        put(M, "_affine_scan", lambda a, b: jnp.zeros_like(b))  # the state that entered each chunk: none
    elif fault == "skip_left_out":
        put(M, "_state_space", lambda p, x, *a, **kw: real_mixer(without(p, "D"), x, *a, **kw))
    elif fault == "conv_bias_left_out":
        put(M, "_state_space", lambda p, x, *a, **kw: real_mixer(without(p, "conv_bias"), x, *a, **kw))
    elif fault == "norm_before_the_gate":
        put(M, "_gated_norm", lambda y, z, weight, eps: M._rms_norm(y, weight, eps) * jax.nn.silu(z))
    elif fault == "norm_over_a_heads_channels":
        def mixer_with_a_norm_a_head(p, x, cfg, dtype):
            def a_head(y, z, weight, eps):  # (..., groups, channels) as (..., groups, heads a group, head size)
                by_head = lambda a: a.reshape(a.shape[:-1] + (-1, cfg.mamba_head_dim))
                return M._rms_norm(by_head(y * jax.nn.silu(z)), by_head(weight), eps).reshape(y.shape)

            real, M._gated_norm = M._gated_norm, a_head
            try:
                return real_mixer(p, x, cfg, dtype)
            finally:
                M._gated_norm = real

        put(M, "_state_space", mixer_with_a_norm_a_head)
    elif fault == "dt_without_its_softplus":
        # the core is handed softplus^-1(step) = dt + dt_bias: a step that can be negative, a decay that can grow
        put(M, "_state_space_core", lambda x, b, c, step, rate, chunk: real_core(x, b, c, jnp.log(jnp.expm1(step)), rate, chunk))
    elif fault == "relu2_as_relu":
        put(M, "_relu2", jax.nn.relu)
    elif fault == "a_rotary_encoding_applied":
        put(M, "_attention", lambda p, x, cfg, *a, **kw: real_attention(
            p, x, dataclasses.replace(cfg, positional_encoding="rope"), *a, **kw))
    elif fault == "scaling_factor_left_out":
        put(M, "_moe_ffn", lambda p, bias, x, cfg, *a, **kw: real_ffn(
            p, bias, x, dataclasses.replace(cfg, routed_scaling_factor=1.0), *a, **kw))
    elif fault == "scaling_factor_on_the_shared_expert_too":
        put(M, "_moe_ffn", lambda p, bias, x, cfg, *a, **kw: real_ffn(
            {**p, "shared": {**p["shared"], "w2": p["shared"]["w2"] * cfg.routed_scaling_factor}}, bias, x, cfg, *a, **kw))
    elif fault == "shared_expert_left_out":
        put(M, "_moe_ffn", lambda p, bias, x, *a, **kw: real_ffn({k: v for k, v in p.items() if k != "shared"}, bias, x, *a, **kw))
    elif fault == "weights_normalised_over_the_held_chosen":
        def over_the_held(router, bias, x, cfg):
            chosen, _, scores = real_route(router, bias, x, cfg)
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            held = (chosen >= cfg.held_experts[0]) & (chosen < cfg.held_experts[1])
            return chosen, picked / (jnp.sum(jnp.where(held, picked, 0.0), axis=-1, keepdims=True) + cfg.route_eps + 1e-9), scores

        put(M, "_route", over_the_held)
    elif fault == "an_expert_outside_the_share":
        def with_a_foreign_expert(p, bias, x, cfg, dtype, **kw):
            out, load, stats = real_ffn(p, bias, x, cfg, dtype, **kw)
            beyond = dataclasses.replace(cfg, held_experts=(cfg.held_experts[1], cfg.held_experts[1] + 1))
            # expert 0's weights stand in, between the share's own latent projections; the shared expert is not added again
            foreign = {**{k: p[k] for k in ("router", "latent_in", "latent_out")}, **{k: p[k][:1] for k in ("w1", "w2")}}
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
    visits = flops.block_visits(length)
    return {"model": {k: m[k] for k in ("layer_types", "held_experts", "held_mamba_heads")}, "length": length,
            "linear_flops_per_token": flops.linear_flops_per_token(m), "visits": visits,
            "core_flops": flops.core_flops(m, visits, 3, 2, 1), "core_bytes": flops.core_bytes(m, 3, length, 2, 1),
            "chunk_macs": flops.state_space_chunk_macs(m, 16),
            "state_space_core_flops": flops.state_space_core_flops(m, 3, length, 16, 2, 1),
            "state_space_core_bytes": flops.state_space_core_bytes(m, 3, length, 16, 2, 1),
            "expert_mm_flops": flops.expert_mm_flops(m, 1000, 4), "expert_mm_bytes": flops.expert_mm_bytes(m, 1000, 4, 15),
            "train_flops": flops.train_flops(m, 216, 1000, length, None, 16)}


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


# -- the tests ----------------------------------------------------------------------------------------

import pytest  # noqa: E402

GROUPS = ("experts", "shared", "latent", "router", "mamba_proj", "mamba_scalars", "attention", "embedding", "head", "norms")


def case(name: str, seed: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "GENTUN_TPU_CACHE_DIR": "off", "PYTHONPATH": ROOT}
    ran = subprocess.run([sys.executable, os.path.abspath(__file__), name, str(seed)], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert ran.returncode == 0, ran.stdout[-3000:] + ran.stderr[-3000:]
    return json.loads(ran.stdout.splitlines()[-1])


def test_a_sound_run_is_correct():
    result = case("sound", 2**31 + 46)
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
    """Hidden 64; blocks M E * E M; 4 Mamba-2 heads of 32 in 2 groups, group 0 (heads 0-1) held, a state of 16, chunks
    of 16; 4 query heads of 16 columns over 2 key-value heads; 16 experts of width 48 in a latent state of 32, 2 held,
    a shared expert of 96; 128 ids; 72 positions, so one block pair a head."""
    got = case("counts", 0)
    assert got["model"] == {"layer_types": ["mamba2", "routed", "full_attention", "routed", "mamba2"],
                            "held_experts": [0, 2], "held_mamba_heads": [0, 2]} and got["length"] == 72
    # a Mamba-2 block's share: z 64, x 64, B 16, C 16, dt 2 columns of W_in and 64 rows of W_out
    mamba = 64 * (64 + 64 + 16 + 16 + 2) + 64 * 64
    attention = 64 * 16 * (4 + 2 * 2) + 4 * 16 * 64
    routed = 64 * 16 + 2 * 64 * 32 + 2 * 64 * 96  # the router, the two latent projections, the shared expert
    linear = 2 * (2 * mamba + attention + 2 * routed)
    assert got["linear_flops_per_token"] == linear == 152_064
    assert got["visits"] == {"pairs": 1, "elements": 72 * 72, "pairs_bwd": 1, "elements_bwd": 72 * 72}
    # a head and sequence: the forward kernel twice (2 products), the backward once (5 products), 2 FLOPs a multiply-add
    per_head = 72 * 72 * 16 * (2 * 2 * 2 + 1 * 2 * 5)
    assert got["core_flops"] == 3 * 4 * per_head
    forward = 4 * (2 * 2 * 16 + 4) + 2 * 2 * 2 * 16  # q read, o written, lse; k, v read
    backward = 4 * (2 * 4 * 16 + 4) + 2 * 2 * 4 * 16  # q, o, do read, dq written, lse; k, v read, dk, dv written
    assert got["core_bytes"] == 3 * 72 * (2 * forward + backward)
    # a held head and chunk of 16: C B' once a group of 2 heads, the masked product with D x, the chunk's state, C S_in
    macs = 16 * 16 * 16 / 2 + 16 * 16 * 32 + 16 * 32 * 16 + 16 * 16 * 32
    assert got["chunk_macs"] == macs == 26_624
    # 3 sequences, 2 held heads, 5 chunks (72 positions), forward twice and the transpose (two products a product) once
    assert got["state_space_core_flops"] == 2 * 3 * 2 * 5 * (2 + 2 * 1) * macs
    operands = 2 * 32 + 2 * 16 + 2  # x of 2 heads, B and C of one group, a step a head: a position
    states = 2 * 5 * 2 * 32 * 16  # a state a held head and chunk, written and read
    assert got["state_space_core_bytes"] == 4 * 3 * (72 * (2 * (operands + 64) + (2 * operands + 64)) + states)
    assert got["expert_mm_flops"] == 4 * 1000 * 2 * 2 * 32 * 48
    # 15 layer-steps as the accepted reader counts them (5 blocks x 3 steps): 6 of them routed; 2 held experts' 2 matrices
    assert got["expert_mm_bytes"] == 4 * (2 * 2 * (32 + 48) * 1000 + 6 * 2 * 2 * 32 * 48 * 2)
    cores = (216 / 72) * (4 * per_head + 2 * 2 * 2 * 5 * 4 * macs)
    assert got["train_flops"] == 216 * (4 * linear + 3 * 2 * 64 * 128) + cores + 4 * 1000 * 2 * 2 * 32 * 48


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


@pytest.mark.parametrize("fault", sorted(MIXER_FAULTS))
def test_a_broken_mixer_is_not_correct(fault):
    _fails_its_check(fault)


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - set(GENERIC_FAULTS) - set(MIXER_FAULTS)))
def test_a_broken_timed_path_is_not_correct(fault):
    _fails_its_check(fault)
