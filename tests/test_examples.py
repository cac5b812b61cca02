"""Smoke tests for the example drivers (VERDICT r3 item 9).

The examples are the reference's de-facto test suite (SURVEY.md §4) — an
API drift that breaks them must not ship green.  Each canonical driver runs
in-process with tiny arguments (synthetic/bundled data, 1 generation, CPU
via conftest's pinning); asserting on stdout keeps the checks behavioral,
not import-only.
"""

import importlib.util
import os
import sys

import pytest

EXAMPLES_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")


def _load_example(name: str):
    path = os.path.join(EXAMPLES_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"example_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    # The examples prepend the repo root to sys.path themselves; importing
    # them never touches sys.argv (main(argv) takes arguments explicitly).
    spec.loader.exec_module(mod)
    return mod


_SMALL_CNN = ["--batch-size", "32", "--dense-units", "16", "--n-images", "96"]

TINY = {
    "mnist_genetic_cnn": [
        "--generations", "1", "--population", "3", "--kfold", "2",
        "--epochs", "1", "--kernels", "4", "4", *_SMALL_CNN,
    ],
    "cifar10_genetic_cnn": [
        "--generations", "1", "--population", "3", "--kfold", "2",
        "--epochs", "1", "--kernels", "4", "4", "4", *_SMALL_CNN,
    ],
    "cifar100_deep": [
        "--generations", "1", "--population", "3",
        "--kernels", "4", "4", "4", *_SMALL_CNN,
    ],
    "uci_boosting_ga": [
        "--generations", "1", "--population", "4", "--kfold", "2",
    ],
}
_TINY_ROUTED = ["--generations", "1", "--population", "3", "--train-steps", "2", "--seq-len", "32", "--n-sequences", "8"]


@pytest.mark.parametrize("name", sorted(TINY))
def test_example_runs_end_to_end(name, capsys):
    mod = _load_example(name)
    mod.main(TINY[name])
    out = capsys.readouterr().out
    assert "best" in out  # every driver prints its best individual


@pytest.mark.parametrize("arch,species", [("lfm2", "Lfm2MoeIndividual"), ("deepseek-v2", "DeepseekV2Individual"),
                                          ("mellum2", "DeepseekV2Individual")])
def test_the_routed_recipe_search_reaches_both_architectures(arch, species, capsys):
    mod = _load_example("routed_recipe_search")
    mod.main(["--arch", arch, *_TINY_ROUTED])
    out = capsys.readouterr().out
    assert f"species {species}" in out and "best recipe" in out
    assert ("bias_step" if arch == "lfm2" else "aux_alpha") in out


def test_distributed_example_demo_runs(capsys):
    mod = _load_example("distributed_search")
    mod.main([
        "demo", "--generations", "1", "--n-images", "96",
        "--kernels", "4", "4", "4", "--batch-size", "32",
    ])
    out = capsys.readouterr().out
    assert "demo best fitness" in out


def test_distributed_example_master_wires_fitness_store():
    """The flagship driver exposes the cross-run store (VERDICT r3 item 7).

    A full master run would block waiting for workers, so this asserts the
    wiring: the CLI flag exists and run_master forwards it to the
    population constructor.
    """
    import inspect

    mod = _load_example("distributed_search")
    assert "--fitness-store" in inspect.getsource(mod.main)
    assert "fitness_store=args.fitness_store" in inspect.getsource(mod.run_master)
