"""The promise ``benchmark/README.md`` makes, kept: a configuration of another
model family is added by new files and manifest entries alone (CPU, pytest).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

- ``BENCHMARK.json`` and ``benchmark/`` are copied to a temporary tree; the
  fixture family under ``fixtures/seam/`` (token sequences, a genome that is
  no bit-string, a fitness that is a negative loss) is laid over the copy and
  its entries appended to the copy's manifest; ``check_manifest.py`` passes
  there, a ``--rehearsal --trace 1`` run of the fixture's cell ends in a result
  line with the fixture's own inputs, checks and reader having run and none of
  the Genetic-CNN's, set-up's four parts sum to ``setup_s``, and every file that
  was there before is byte-identical;
- ``check_manifest.py`` refuses, with a line that says why, a configuration
  without ``family``, a family without its directory, its files or one of the
  four functions, a family directory outside ``paths``, and a reader under
  ``layer_metrics/`` that no entry names.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, "tests", "fixtures", "seam")
LEFT_BEHIND = shutil.ignore_patterns("out", "__pycache__", "*.pyc")  # what running leaves, as .gitignore lists it
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "GENTUN_TPU_CACHE_DIR": "off",
       "PYTHONPATH": ROOT}  # the program under test is not part of the benchmark's tree


def copy_of_the_benchmark(tmp_path) -> str:
    tree = str(tmp_path / "tree")
    os.makedirs(tree)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    shutil.copytree(BENCH, os.path.join(tree, "benchmark"), ignore=LEFT_BEHIND)
    return tree


def hashes(tree: str) -> dict:
    out = {}
    for folder, dirs, files in os.walk(tree):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, tree)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def manifest_of(tree: str) -> dict:
    with open(os.path.join(tree, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def write_manifest(tree: str, manifest: dict) -> None:
    with open(os.path.join(tree, "BENCHMARK.json"), "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=1)


def run_in(tree: str, *argv: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, *argv], cwd=tree, env=ENV, capture_output=True, text=True, timeout=300)


def test_a_second_family_is_added_by_files_and_entries_alone(tmp_path):
    tree = copy_of_the_benchmark(tmp_path)
    before, manifest_before = hashes(tree), manifest_of(tree)

    # The addition: new files only, new entries only.
    for folder, _, files in os.walk(os.path.join(FIXTURE, "benchmark")):
        for name in files:
            source = os.path.join(folder, name)
            target = os.path.join(tree, os.path.relpath(source, FIXTURE))
            assert not os.path.exists(target), f"the fixture would overwrite {target}"
            os.makedirs(os.path.dirname(target), exist_ok=True)
            shutil.copy(source, target)
    with open(os.path.join(FIXTURE, "entries.json"), encoding="utf-8") as fh:
        entries = json.load(fh)
    manifest = manifest_of(tree)
    for key, added in entries.items():
        manifest[key] = manifest[key] + added
    write_manifest(tree, manifest)

    checked = run_in(tree, "benchmark/check_manifest.py")
    assert checked.returncode == 0, checked.stdout + checked.stderr
    assert f"ok ({len(manifest_before['workloads']) + 1} cells" in checked.stdout

    ran = run_in(tree, "benchmark/run.py", "--workload", "tiny_lm.seqeval", "--seed", str(2**31 + 5),
                 "--seconds", "1", "--trace", "1", "--rehearsal")
    assert ran.returncode == 0, ran.stdout[-4000:] + ran.stderr[-4000:]
    lines = ran.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True and result["attempted"] > 0 and result["failed"] == 0
    # the fixture family's own four functions and its reader are the ones that ran ...
    for part in ("make_inputs", "program_side", "window_checks", "after_window", "reader"):
        assert f"info tiny_lm {part}" in lines, part
    said = [l.split(":")[0] for l in lines if l.startswith("check ")]
    assert said == ["check units_in_window", "check fitness_is_a_negative_loss", "check order_diff", "check loss_gap"]
    assert all(l.endswith(" ok") for l in lines if l.startswith("check "))
    # ... its fitness is no accuracy (every one below zero), and its checks say the run is sound
    rehearsal = next(l for l in lines if l.startswith("rehearsal (no measurement): checks say "))
    assert rehearsal.startswith("rehearsal (no measurement): checks say True ")
    metrics = json.loads(rehearsal.split("checks say True ", 1)[1])
    assert list(metrics) == ["setup_warmup_s", "tiny_lm_loss_mean"] and metrics["tiny_lm_loss_mean"]["value"] > 0.0
    # ... and set-up's four parts, which the harness reads off its own clock in any family's cell, sum to setup_s
    setup_s, *parts = map(float, re.search(r"^set-up: (\S+) s \(backend (\S+) s, inputs (\S+) s, warm-up (\S+) s, "
                                           r"program's check (\S+) s\)", ran.stdout, re.M).groups())
    assert abs(sum(parts) - setup_s) < 0.003 and abs(metrics["setup_warmup_s"]["value"] - parts[2]) < 0.001, (setup_s, parts)

    # Nothing that was there was edited: every file byte-identical, every old entry in place.
    after, manifest_after = hashes(tree), manifest_of(tree)
    assert {k: v for k, v in after.items() if k in before and k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    for key, value in manifest_before.items():
        assert manifest_after[key][:len(value)] == value if isinstance(value, list) else manifest_after[key] == value
    # The fixture is a test of the harness, never a cell of the benchmark.
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        assert "tiny_lm" not in fh.read()


def _edit_json(path: str, change) -> None:
    with open(path, encoding="utf-8") as fh:
        value = json.load(fh)
    change(value)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(value, fh)


def _no_family(tree):
    _edit_json(os.path.join(tree, "benchmark", "configs", "c10_flagship.json"), lambda c: c.pop("family"))


def _no_directory(tree):
    _edit_json(os.path.join(tree, "benchmark", "configs", "c100_deep.json"),
               lambda c: c.update(family="no_such_family"))


def _no_reference(tree):
    os.remove(os.path.join(tree, "benchmark", "families", "genetic_cnn", "reference.py"))


def _without(function):
    """``family.py`` of the Genetic-CNN with one of the four names gone: the two
    it defines renamed, the two it takes from its ``correct.py`` not imported."""
    def edit(tree):
        path = os.path.join(tree, "benchmark", "families", "genetic_cnn", "family.py")
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
        imported = {"after_window": "program_side", "program_side": "after_window"}
        if function in imported:
            edited = source.replace("from correct import after_window, program_side",
                                    f"from correct import {imported[function]}")
        else:
            edited = source.replace(f"def {function}(", f"def _{function}(")
        assert edited != source
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(edited)
    return edit


def _outside_paths(tree):
    _edit_json(os.path.join(tree, "BENCHMARK.json"),
               lambda m: m.update(paths=["benchmark/configs", "benchmark/traffic"]))


def _reader_left_behind(tree):
    """An entry taken out of the manifest (as ``setup_oom_attempt_s`` was) whose reader stayed."""
    with open(os.path.join(tree, "benchmark", "layer_metrics", "setup_oom_attempt_s.py"), "w", encoding="utf-8") as fh:
        fh.write("def read(run):\n    return None\n")


@pytest.mark.parametrize("edit,why", [
    (_no_family, "config c10_flagship: its file states no 'family'"),
    (_no_directory, "config c100_deep: family 'no_such_family' has no directory benchmark/families/no_such_family/"),
    (_no_reference, "family 'genetic_cnn' lacks reference.py"),
    (_without("make_inputs"), "benchmark/families/genetic_cnn/family.py lacks make_inputs"),
    (_without("program_side"), "benchmark/families/genetic_cnn/family.py lacks program_side"),
    (_without("after_window"), "benchmark/families/genetic_cnn/family.py lacks after_window"),
    (_without("window_checks"), "benchmark/families/genetic_cnn/family.py lacks window_checks"),
    (_outside_paths, "family directory benchmark/families/genetic_cnn/ is outside paths"),
    (_reader_left_behind, "layer_metrics/ holds readers no entry names: ['setup_oom_attempt_s']"),
], ids=["no_family", "no_directory", "no_reference", "no_make_inputs", "no_program_side", "no_after_window",
        "no_window_checks", "outside_paths", "reader_left_behind"])
def test_check_manifest_refuses_a_family_that_is_not_whole(tmp_path, edit, why):
    tree = copy_of_the_benchmark(tmp_path)
    assert run_in(tree, "benchmark/check_manifest.py").returncode == 0
    edit(tree)
    checked = run_in(tree, "benchmark/check_manifest.py")
    assert checked.returncode == 1
    assert why in checked.stdout, checked.stdout
