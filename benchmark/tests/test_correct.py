"""The comparison that decides ``correct`` can fail (CPU, rehearsal sizes).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

- the control -- the reference computed in fp8, put in the program's place --
  comes out over the configuration's limit, and the sound program under it;
- a whole run of the harness (the look for a chip skipped, rehearsal sizes)
  is ``correct`` as it stands and not ``correct`` with the timed path broken
  underneath: a train step that returns its state unchanged, a validation
  pass that answers class 0 for whole slots (PR 21's miscompile), and a
  fitness that depends on the slot it was computed in.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("GENTUN_TPU_CACHE_DIR", "off")

import run as harness  # noqa: E402

CELL = "c10_flagship.popeval"
family = harness.load_family("genetic_cnn")


def args(seed: int, cell: str = CELL) -> argparse.Namespace:
    return argparse.Namespace(workload=cell, seed=seed, seconds=0.5, trace=0, rehearsal=True)


def checks_of(result):
    return {c["name"]: c for c in result["checks"]}


def test_a_sound_run_is_correct():
    result = harness.run(args(21))
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_the_fp8_control_is_over_the_limit():
    """Same weights, rows and masks; the reference in fp8 in the program's place."""
    _, _, config, mix = harness.load_cell(CELL, rehearsal=True)
    # wide enough that the rounding, not a single flipped ReLU, is what is read
    config = harness.merge(config, {"model": {"kernels_per_layer": [8, 16, 32], "dense_units": 32,
                                              "batch_size": 32, "compute_dtype": "bfloat16"},
                                    "data": {"n": 320}})
    limit = config["check"]["limits"]["logit_gap"]
    for seed in (31, 32, 33):
        ctx = harness.Ctx(config=config, seed=seed, **family.make_inputs(config, mix, seed, rehearsal=True))
        sound, control = family.after_window(ctx, family.program_side(ctx), "fp8")
        sound = {c["name"]: c["value"] for c in sound}
        assert sound["logit_gap"] <= limit < control["logit_gap"], (seed, sound, control)
        assert sound["eval_flip"] < control["eval_flip"], (seed, sound, control)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from gentun_tpu.models import cnn

    real = cnn._fold_segment_fns

    def broken(*key):
        init_pop, _train_pop, eval_pop = real(*key)
        return init_pop, (lambda p, opt, masks, x, y, seg, rng: (p, opt, rng)), eval_pop

    monkeypatch.setattr(cnn, "_fold_segment_fns", broken)
    result = harness.run(args(22))
    assert not result["correct"]
    assert not checks_of(result)["dparam_gap"]["ok"]


def test_a_validation_pass_that_answers_class_0_in_some_slots_is_not_correct(monkeypatch):
    """In the deep cell, where every fitness is near chance, at both widths."""
    from gentun_tpu.models import cnn

    real = cnn._fold_segment_fns

    def broken(*key):
        init_pop, train_pop, eval_pop = real(*key)

        def eval_some_slots_class_0(params, masks, x, y, val_idx, val_weight):
            acc = np.array(eval_pop(params, masks, x, y, val_idx, val_weight))
            rows = np.asarray(val_idx)[np.asarray(val_weight) > 0]
            acc[::2] = float((np.asarray(y)[rows] == 0).mean())  # every other slot answers class 0
            return acc

        return init_pop, train_pop, eval_some_slots_class_0

    monkeypatch.setattr(cnn, "_fold_segment_fns", broken)
    result = harness.run(args(24, "c100_deep.popeval"))
    assert not result["correct"]
    assert not checks_of(result)["eval_flip"]["ok"]


def test_a_sound_run_of_the_deep_cell_is_correct():
    result = harness.run(args(25, "c100_deep.popeval"))
    assert result["correct"], result["checks"]


def test_a_fitness_that_depends_on_its_slot_is_not_correct(monkeypatch):
    from gentun_tpu.models import cnn

    real = cnn.GeneticCnnModel.cross_validate_population.__func__

    def tilted(cls, x, y, genomes, **config):
        out = np.asarray(real(cls, x, y, genomes, **config), np.float64)
        return out * np.linspace(0.5, 1.0, len(out))  # what PR 21's miscompile did, mildly

    monkeypatch.setattr(cnn.GeneticCnnModel, "cross_validate_population", classmethod(tilted))
    result = harness.run(args(23))
    assert not result["correct"]
    assert not checks_of(result)["slot_diff"]["ok"]
