"""PR 25: names on the device's work, host phases under every evaluation call,
and the trace reader that turns both into seconds per op class.

- every scope of the vocabulary (docs/OBSERVABILITY.md) and every conv module
  name is in the ``op_name`` metadata of the compiled train and eval programs,
  forward and backward;
- scopes and spans are metadata: fitness is bit-identical with telemetry on,
  off, and to the values the parent commit gave;
- off, ``phase`` is the spans module's no-op singleton and nothing is
  recorded; on, one ``cv_call`` per chunk with its children in order,
  ``dispatch_s <= dur_s`` on every device span, an ``oom_attempt`` span when
  the healer splits; a handful of programs asked for before a fresh
  configuration's first train dispatch (PR 26: the eager head stays gone);
- ``benchmark/scope_reduce.py``: classification and arithmetic on
  ``benchmark/fixtures/scope_fixture.json``, and its reading of the protobuf
  wire format against a trace this jax writes.
"""

from __future__ import annotations

import ast
import glob
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import scope_reduce  # noqa: E402

from gentun_tpu.models import cnn, evaluation  # noqa: E402
from gentun_tpu.models.cnn import GeneticCnnModel  # noqa: E402
from gentun_tpu.telemetry import spans  # noqa: E402

NODES, FILTERS = (3, 2), (4, 8)
KW = dict(nodes=NODES, kernels_per_layer=FILTERS, kfold=2, epochs=(1,), learning_rate=(0.01,),
          batch_size=8, dense_units=16, cache_dir=False, seed=3)
GENOMES = [{"S_1": (1, 0, 1), "S_2": (1,)}, {"S_1": (0, 0, 0), "S_2": (0,)}, {"S_1": (1, 1, 1), "S_2": (0,)}]
#: What the parent commit (9db80c1, before any scope or span of this PR) returned
#: for these genomes, data and configuration on the CPU.
PARENT_FITNESS = [0.140625, 0.03125, 0.015625]

MODEL = "MaskedGeneticCnn"
CONV_MODULES = [f"stage{s}_entry" for s in range(len(NODES))] + [
    f"stage{s}_node{j}" for s, k in enumerate(NODES) for j in range(k)]
MODEL_SCOPES = [f"stage{s}/{part}" for s in range(len(NODES))
                for part in ("mask_sum", "gate", "merge", "pool")] + ["head"]


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    return (rng.normal(size=(64, 8, 8, 3)).astype(np.float32), rng.integers(0, 10, 64).astype(np.int32))


class Records:
    def __init__(self):
        self.items = []

    def record(self, rec):
        self.items.append(rec)


@pytest.fixture
def telemetry():
    """Telemetry on with a sink of its own; everything back as it was after."""
    sink = Records()
    seen = set(evaluation._seen_programs)
    spans.set_run_sink(sink)
    spans.enable()
    try:
        yield sink
    finally:
        spans.disable()
        spans.set_run_sink(None)
        evaluation._seen_programs.clear()
        evaluation._seen_programs.update(seen)


def of_kind(sink, *kinds):
    return [r for r in sink.items if r.get("type") == "span" and r["kind"] in kinds]


# -- A. the names ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def op_names():
    """``op_name`` metadata of the compiled train and eval programs."""
    import jax
    import jax.numpy as jnp

    from gentun_tpu.ops.dag import stack_genome_masks

    key = (NODES, FILTERS, 16, 10, 0.5, "bfloat16", (1,), (0.01,), 0.9, False, 8, 32, 16, False, 16, 1)
    init_pop, train_pop, eval_pop = cnn._fold_segment_fns(*key)
    genomes = GENOMES[:2]
    masks = [{k: jnp.asarray(v) for k, v in stage.items()} for stage in stack_genome_masks(genomes, NODES)]
    model = cnn.MaskedGeneticCnn(nodes=NODES, filters=FILTERS, dense_units=16, n_classes=10)
    _, ((p, _),) = cnn._fold_carries({"seed": 0, "input_shape": (8, 8, 3)}, model, masks,
                                     evaluation.genome_hashes(genomes), 1, None)
    x, y = jnp.zeros((48, 8, 8, 3)), jnp.zeros((48,), jnp.int32)
    train = train_pop.lower(p, init_pop(p), masks, x, y, jnp.zeros((4, 8), jnp.int32),
                            jnp.zeros((2, 2), jnp.uint32)).compile().as_text()
    evaluate = eval_pop.lower(p, masks, x, y, jnp.zeros((16,), jnp.int32),
                              jnp.ones((16,), jnp.float32)).compile().as_text()
    return {"train": set(re.findall(r'op_name="([^"]*)"', train)),
            "eval": set(re.findall(r'op_name="([^"]*)"', evaluate))}


def has(names, fragment):
    return any(fragment in n for n in names)


@pytest.mark.parametrize("scope", MODEL_SCOPES + CONV_MODULES)
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_train_program_names_the_model(op_names, scope, direction):
    under = f"jvp({MODEL})" if direction == "forward" else f"transpose(jvp({MODEL}))"
    assert has(op_names["train"], f"{under}/{scope}/")


@pytest.mark.parametrize("fragment", ["jvp(loss)/", "transpose(jvp(loss))/", "/optimizer/", "/gather/"])
def test_train_program_names_what_flax_does_not_own(op_names, fragment):
    assert has(op_names["train"], fragment)


@pytest.mark.parametrize("fragment", [f"/{MODEL}/{s}/" for s in MODEL_SCOPES + CONV_MODULES]
                         + ["/gather/", "/score/"])
def test_eval_program_names(op_names, fragment):
    assert has(op_names["eval"], fragment)


@pytest.mark.parametrize("program", ["train", "eval"])
def test_every_compiled_name_gets_a_class(op_names, program):
    """The reader's rules on real names: nothing of the model lands in
    ``rest``, nothing outside it in a model class, every conv module is seen."""
    seen = {}
    for name in op_names[program]:
        klass, detail = scope_reduce.classify(name)
        assert klass in scope_reduce.CLASSES and klass != "unattributed"
        assert (MODEL in name) == (klass != "rest"), name
        seen.setdefault(klass, set()).add(detail)
    convs = seen["conv_fwd"] | seen.get("conv_bwd", set())
    assert convs == set(CONV_MODULES)
    assert {"mask_sum", "gate", "merge", "pool"} <= seen["glue"]


# -- B. spans ---------------------------------------------------------------------------


def test_fitness_same_with_telemetry_on_off_and_as_the_parent(data, telemetry):
    on = GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    spans.disable()
    off = GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    assert on.tolist() == off.tolist() == PARENT_FITNESS


def test_off_the_helper_is_the_noop_and_nothing_is_recorded(data):
    assert not spans.enabled()
    noop = spans.span("anything")
    assert evaluation.phase("train", {"pop": 2}, program=("never", "seen")) is noop
    assert ("never", "seen") not in evaluation._seen_programs
    marker = object()
    assert noop.fence(marker) is marker
    sink = Records()
    spans.set_run_sink(sink)
    try:
        GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    finally:
        spans.set_run_sink(None)
    assert sink.items == []


def test_one_cv_call_with_its_children_in_order(data, telemetry):
    GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    (call,) = of_kind(telemetry, "cv_call")
    assert call["attrs"] == {"n_real": 3, "pop": 4}
    children = sorted((r for r in telemetry.items if r.get("parent_id") == call["span_id"]),
                      key=lambda r: r["t_wall"])
    kinds = [r["attrs"].get("phase", r["kind"]) if r["kind"] == "compile" else r["kind"] for r in children]
    assert kinds == ["prepare", "index_build", "init_params", "dataset",
                     "fold_slice", "train", "eval", "fold_slice", "train", "eval", "fetch"]
    assert [r["attrs"]["fold"] for r in children if r["kind"] == "fold_slice"] == [0, 1]
    assert next(r for r in children if r["kind"] == "dataset")["attrs"]["source"] in ("uploaded", "found")
    # only the device spans and fold_slice carry a fold: every reader of PR 24 picks device spans by it
    assert {r["kind"] for r in telemetry.items if "fold" in (r.get("attrs") or {})} <= {
        "train", "eval", "compile", "fold_slice"}


def test_device_spans_split_dispatch_from_wait(data, telemetry):
    for _ in range(2):  # the first call of a shape is `compile`, the second `train`/`eval`
        GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    device = of_kind(telemetry, "train", "eval", "compile")
    assert {r["kind"] for r in device} >= {"compile", "train"}
    assert len(device) == 8
    for r in device:
        assert 0.0 < r["attrs"]["dispatch_s"] <= r["dur_s"]
        assert ("phase" in r["attrs"]) == (r["kind"] == "compile")
    assert all(r["attrs"]["carry_devices"] >= 1 for r in device if r["attrs"].get("phase", r["kind"]) == "train")


#: Programs a fresh configuration may ask the backend for between the start of
#: its first ``cv_call`` and its first train dispatch (PR 26): the carry builder,
#: ``init_pop``, and the four tiny ones behind the two base keys (once a
#: process).  The parent asked for 34 here: a slice per parameter leaf shape and
#: the eager key chains.
HEAD_PROGRAMS = 8


def test_a_fresh_configuration_asks_for_a_handful_of_programs_before_its_first_train(data, telemetry):
    import time

    import jax

    compiles = []  # jax has no public way to take a listener back; this one costs an append a compile

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.time())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    # widths no other test of this process uses: every eager op on a parameter leaf would be a program of its own
    fresh = {**KW, "kernels_per_layer": (5, 7), "dense_units": 11, "mesh": None}
    GeneticCnnModel.cross_validate_population(*data, GENOMES, **fresh)
    (call,) = of_kind(telemetry, "cv_call")
    device = of_kind(telemetry, "compile")
    assert [r["attrs"]["phase"] for r in device] == ["train", "eval"]  # fold 1 reuses both
    head = [t for t in compiles if call["t_wall"] <= t <= device[0]["t_wall"]]
    assert 2 <= len(head) <= HEAD_PROGRAMS and len(compiles) <= HEAD_PROGRAMS + 2
    first = len(compiles)
    GeneticCnnModel.cross_validate_population(*data, GENOMES[::-1], **fresh)
    assert len(compiles) == first  # a shape already seen asks for nothing


def test_an_oom_the_healer_cures_leaves_an_oom_attempt_span(data, telemetry, monkeypatch):
    monkeypatch.setattr(cnn, "_POP_PROGRAM_CAP", {})
    real = GeneticCnnModel._cross_validate_population_one.__func__

    def too_big_above_two(cls, x, y, genomes, **config):
        if len(genomes) > 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory (made up by the test)")
        return real(cls, x, y, genomes, **config)

    monkeypatch.setattr(GeneticCnnModel, "_cross_validate_population_one", classmethod(too_big_above_two))
    fitness = GeneticCnnModel.cross_validate_population(*data, GENOMES + GENOMES[:1], **KW)
    assert fitness.tolist() == PARENT_FITNESS + PARENT_FITNESS[:1]
    (attempt,) = of_kind(telemetry, "oom_attempt")
    assert attempt["attrs"] == {"genomes": 4, "cap": 2} and attempt["dur_s"] >= 0.0
    (event,) = [r for r in telemetry.items if r.get("type") == "event" and r["name"] == "oom_split"]
    assert event["data"] == {"genomes": 4, "cap": 2}
    assert [c["attrs"]["n_real"] for c in of_kind(telemetry, "cv_call")] == [2, 2]  # one per chunk


def test_a_device_call_that_raises_is_no_compile_span(telemetry):
    """``first_call_s`` and the other readers take `compile`/`train`/`eval`
    for calls that returned; the shape stays unseen, so its next call is the
    first."""
    program = ("a shape", "never seen")
    with pytest.raises(RuntimeError):
        with evaluation.phase("train", {"pop": 50, "fold": 0}, program=program):
            raise RuntimeError("RESOURCE_EXHAUSTED (made up by the test)")
    (failed,) = [r for r in telemetry.items if r.get("type") == "span"]
    assert failed["kind"] == "call_failed" and failed["error"] == "RuntimeError"
    assert failed["attrs"] == {"pop": 50, "fold": 0, "phase": "train"}
    assert program not in evaluation._seen_programs
    with pytest.raises(ValueError):  # a host phase keeps its kind
        with evaluation.phase("prepare"):
            raise ValueError("bad config")
    assert telemetry.items[-1]["kind"] == "prepare" and telemetry.items[-1]["error"] == "ValueError"


def test_eval_timer_emits_no_span(telemetry):
    from gentun_tpu.utils import EvalTimer

    timer = EvalTimer()
    with timer.measure(3, label="x"):
        pass
    assert timer.total_individuals == 3 and telemetry.items == []


def test_spans_module_imports_no_jax_at_import_time():
    with open(spans.__file__, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not any("jax" in (a.name if isinstance(n, ast.Import) else n.module or "")
                   for n in top for a in n.names)


# -- C. the reader ----------------------------------------------------------------------

with open(os.path.join(ROOT, "benchmark", "fixtures", "scope_fixture.json"), encoding="utf-8") as _fh:
    FIXTURE = json.load(_fh)


@pytest.fixture(scope="module")
def reduced():
    ops = {p: [tuple(o) for o in intervals] for p, intervals in FIXTURE["ops"].items()}
    return scope_reduce.seconds_per_class(ops, FIXTURE["programs"], FIXTURE["fallback"])


@pytest.mark.parametrize("op_name,klass,detail", FIXTURE["classify"])
def test_classify(op_name, klass, detail):
    assert scope_reduce.classify(op_name) == (klass, detail)


@pytest.mark.parametrize("program,klass", [(p, c) for p in FIXTURE["expect"] for c in scope_reduce.CLASSES])
def test_seconds_per_class_on_the_fixture(reduced, program, klass):
    """Program 1: a fusion with a convolution goes to the convolution's class
    whatever the rest of it says, one without goes to its majority, one with
    no metadata and an op the table lacks are unattributed, a ``while`` keeps
    only what its body leaves.  Program 2: the parent's names, no scopes.
    Program 4: no HLO table, the event's own ``tf_op`` decides."""
    assert reduced[program]["classes"][klass] == FIXTURE["expect"][program][klass]


@pytest.mark.parametrize("program", sorted(FIXTURE["expect_details"]))
def test_details_on_the_fixture(reduced, program):
    assert reduced[program]["details"] == FIXTURE["expect_details"][program]


def test_fusion_rule_one_by_one():
    table = FIXTURE["programs"]["jit_train_segment(1)"]
    assert scope_reduce.classify_instruction(table["fusion.1"]) == ("conv_bwd", "stage0_node1")
    assert scope_reduce.classify_instruction(table["fusion.2"]) == ("glue", "mask_sum")
    assert scope_reduce.classify_instruction(table["fusion.3"]) == ("unattributed", "")
    assert scope_reduce.classify_instruction(table["fusion.6"]) == ("rest", "loss")  # its own name, no vote inside
    assert scope_reduce.instruction_of("%fusion.512 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kLoop") == "fusion.512"
    assert scope_reduce.base_name("jit_train_segment(10897651824952207794)") == "jit_train_segment"


def test_reader_against_a_trace_this_jax_writes(data, telemetry, tmp_path):
    """The wire-format reader against what the installed profiler writes: the
    HLO of the cell's programs is in the trace with the model's names on it,
    and the ``gentun/`` annotations carry the individuals of each call."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    trace = scope_reduce.read(path)
    # (one table per program shape this process has loaded, not only this call's)
    assert {scope_reduce.base_name(n) for n in trace["hlo_tables"]} == {scope_reduce.EVAL, scope_reduce.TRAIN}
    # (since PR 38 the host sampler's ``gentun/tick`` annotations lie among the call's, every 20 ms)
    assert [a["kind"] for a in trace["annotations"] if a["kind"] != "tick"][:5] == [
        "cv_call", "prepare", "index_build", "init_params", "dataset"]
    assert scope_reduce.individuals_traced(trace, {}) == 3
    with open(path, "rb") as fh:
        raw = fh.read()
    train = [scope_reduce.hlo_instructions(stats["Hlo Proto"])
                for plane, names, metadata in scope_reduce._planes(raw) if plane == "/host:metadata"
                for name, stats in (scope_reduce._event_metadata(m, names) for m in metadata)
                if scope_reduce.base_name(name) == scope_reduce.TRAIN][0]
    classes = {scope_reduce.classify_instruction(i)[0] for i in train.values() if i["op_name"] or i.get("body")}
    assert {"conv_fwd", "conv_bwd", "glue", "head", "rest"} <= classes
    fused = [i for i in train.values() if i.get("body")]
    assert fused and all(isinstance(op, str) and isinstance(name, str) for i in fused for op, name in i["body"])
