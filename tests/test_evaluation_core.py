"""What the two jax families share lives under neither (``models/evaluation.py``).

- both families call the same function objects for hashes, keys, phases and
  the prelude, so a program first seen through either is a ``compile`` once;
- ``evaluation_prelude`` treats every spelling of "no cache" alike and runs the
  fleet's publish hooks, from either family's ``cross_validate_population``;
- the options the one executor made meaningless are unknown parameters;
- no family imports the other, and the shared module imports neither (nor flax).
"""

from __future__ import annotations

import ast
import os

import numpy as np
import pytest

from gentun_tpu.models import cnn, evaluation, lfm2_moe
from gentun_tpu.models.cnn import GeneticCnnModel
from gentun_tpu.models.lfm2_moe import Lfm2MoeModel
from gentun_tpu.telemetry import spans
from gentun_tpu.utils import xla_cache

MODELS = os.path.dirname(os.path.abspath(evaluation.__file__))
GENOME = {"S_1": (1, 0, 1), "S_2": (1,)}
IMAGES = (np.zeros((16, 8, 8, 1), np.float32), np.arange(16, dtype=np.int32) % 2)
RECIPE = dict(log10_lr=-2.5, warmup_frac=0.5, weight_decay=0.1, beta2=0.95, bias_step=0.01)
TOKENS = (np.zeros((6, 16), np.int32), np.zeros((6, 16), np.int32))


# -- one set of function objects --------------------------------------------------------


@pytest.mark.parametrize("name", ["genome_hashes", "base_keys", "phase", "evaluation_prelude"])
def test_both_families_call_the_same_function_object(name):
    shared = getattr(evaluation, name)
    assert getattr(cnn, name) is shared and getattr(lfm2_moe, name) is shared


def test_the_cnn_derives_its_fold_keys_with_the_shared_function():
    assert cnn.fold_content_keys is evaluation.fold_content_keys


def test_a_program_first_seen_through_either_family_is_a_compile_once():
    records = []
    program = ("a program", "of no family")

    class Sink:
        record = staticmethod(records.append)

    spans.set_run_sink(Sink)
    spans.enable()
    try:
        with cnn.phase("train", {"pop": 2}, program=program):
            pass
        with lfm2_moe.phase("train", {"pop": 1}, program=program):
            pass
    finally:
        spans.disable()
        spans.set_run_sink(None)
        evaluation._seen_programs.discard(program)
    kinds = [(r["kind"], r["attrs"].get("phase")) for r in records if r.get("type") == "span"]
    assert kinds == [("compile", "train"), ("train", None)]


# -- the prelude, from either entry point -----------------------------------------------


class Stop(Exception):
    """Raised where the prelude ends, so a case costs no training."""


def cnn_call(cache_dir):
    return GeneticCnnModel.cross_validate_population(
        *IMAGES, [GENOME], nodes=(3, 2), kernels_per_layer=(4, 4), kfold=2, cache_dir=cache_dir)


def lfm2_call(cache_dir):
    return Lfm2MoeModel.cross_validate_population(
        *TOKENS, [RECIPE], batch_sequences=2, eval_sequences=2, cache_dir=cache_dir)


@pytest.mark.parametrize("call", [cnn_call, lfm2_call], ids=["genetic_cnn", "lfm2_moe"])
@pytest.mark.parametrize("cache_dir,enabled", [
    (None, "the default"), (False, None), ("", None), ("0", None), ("off", None), ("none", None),
    (" Disabled ", None), ("a/path", "a/path")], ids=["None", "False", "empty", "0", "off", "none", "disabled", "path"])
def test_prelude_is_the_same_from_either_family(monkeypatch, call, cache_dir, enabled):
    seen = {"enabled": [], "published": 0}

    def hook():
        seen["published"] += 1

    def stop():
        raise Stop

    monkeypatch.setattr(xla_cache, "default_cache_dir", lambda: "the default")
    monkeypatch.setattr(evaluation, "enable_compilation_cache", seen["enabled"].append)
    monkeypatch.setattr(evaluation, "mark_backend_used", stop)  # the prelude's last step
    xla_cache.register_publish_hook(hook)
    try:
        with pytest.raises(Stop):
            call(cache_dir)
    finally:
        xla_cache.unregister_publish_hook(hook)
    assert seen == {"enabled": [os.path.abspath(enabled)] if enabled else [], "published": 1}


# -- the options that went --------------------------------------------------------------


@pytest.mark.parametrize("option", ["fold_parallel", "entry_channel_pad"])
@pytest.mark.parametrize("through", ["constructor", "population_call"])
def test_an_option_of_the_deleted_executor_is_an_unknown_parameter(option, through):
    with pytest.raises(TypeError, match=option):
        if through == "constructor":
            GeneticCnnModel(*IMAGES, GENOME, **{option: 8})
        else:
            GeneticCnnModel.cross_validate_population(*IMAGES, [GENOME], **{option: 8})


# -- who imports whom -------------------------------------------------------------------


def imported_modules(filename):
    with open(os.path.join(MODELS, filename), encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):  # the names may be modules: `from . import cnn`
            found.add(node.module or "")
            found.update(f"{node.module or ''}.{alias.name}" for alias in node.names)
    return found


@pytest.mark.parametrize("filename,forbidden", [
    ("lfm2_moe.py", ("cnn", "flax")), ("cnn.py", ("lfm2_moe",)), ("evaluation.py", ("cnn", "lfm2_moe", "flax"))])
def test_no_family_imports_the_other_and_the_shared_module_neither(filename, forbidden):
    modules = imported_modules(filename)
    assert modules  # the walk found the file's imports
    for name in forbidden:
        assert not [m for m in modules if name in m.split(".")], (filename, name, sorted(modules))
