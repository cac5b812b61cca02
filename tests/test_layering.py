"""The package's arrows point one way.

Read from every module's ``ast`` (nothing is imported, jax least of all): each
``import`` and ``from ... import`` of ``gentun_tpu``, the ones inside functions
too -- a lazy import hides a cycle from the interpreter, not from the reader.
The seven ``__init__.py`` are left out: they re-export.  One case a module holds
its imports to the table of layers below; one more holds the whole graph to have
no cycle.  ``tests/test_evaluation_core.py`` keeps the rule of
``models/evaluation.py`` (which also forbids flax); it is not repeated here.
"""

from __future__ import annotations

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "gentun_tpu"


def _modules() -> dict:
    """{"distributed.broker": path} of every module but the ``__init__.py``."""
    found = {}
    for directory, _, files in os.walk(os.path.join(ROOT, PACKAGE)):
        for name in files:
            if name.endswith(".py") and name != "__init__.py":
                path = os.path.join(directory, name)
                found[os.path.relpath(path, os.path.join(ROOT, PACKAGE))[:-3].replace(os.sep, ".")] = path
    return dict(sorted(found.items()))


MODULES = _modules()


def _imports(module: str) -> set:
    """The modules of the package that ``module`` imports, anywhere in its source."""
    with open(MODULES[module]) as fh:
        tree = ast.parse(fh.read())
    here = [PACKAGE] + module.split(".")[:-1]
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            targets = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else []
            target = ".".join(base + ([node.module] if node.module else []))
            # ``from .x import y``: y may be a module of the package x, or a name of the module x
            targets = [target] if target[len(PACKAGE) + 1:] in MODULES else [f"{target}.{alias.name}" for alias in node.names]
        else:
            continue
        for target in targets:
            if target == PACKAGE or target.startswith(PACKAGE + "."):
                name = target[len(PACKAGE) + 1:]
                while name and name not in MODULES:  # a name of a module, or of a package's __init__
                    name = name.rpartition(".")[0]
                found.add(name or target[len(PACKAGE) + 1:].split(".")[0])
    found.discard(module)
    return found


def _layer(module: str) -> str:
    return module.split(".")[0]


BELOW_THE_MODELS = {"ops", "parallel", "telemetry", "utils"}
#: layer: the layers it may import from (its own included where listed)
MAY_IMPORT = {
    "utils": {"utils"},
    "ops": {"ops"},
    "parallel": {"parallel"},
    "telemetry": {"telemetry", "utils"},
    "models": {"models"} | BELOW_THE_MODELS,
    "genes": set(),
    "individuals": {"genes", "models"} | BELOW_THE_MODELS,
    "populations": {"genes", "individuals", "models"} | BELOW_THE_MODELS,
    "surrogate": {"genes", "individuals", "populations", "models"} | BELOW_THE_MODELS,
    "algorithms": {"genes", "individuals", "populations", "surrogate", "models"} | BELOW_THE_MODELS,
    "algorithms_async": {"genes", "individuals", "populations", "surrogate", "models"} | BELOW_THE_MODELS,
    "distributed": {"distributed", "genes", "individuals", "populations", "surrogate", "algorithms", "algorithms_async",
                    "models"} | BELOW_THE_MODELS,
}
#: the one arrow that points up, under the name of its debt
EXCEPTIONS = {("telemetry.canary", "distributed.sessions"): "ROADMAP D4: the canary plane probes through a session client"}
#: a model family's own modules; ``evaluation`` and ``generic`` serve every family
FAMILIES = {"cnn": {"cnn"}, "lfm2_moe": {"lfm2_moe", "delta_kernel", "sparse_kernel"}, "delta_kernel": {"delta_kernel"},
            "sparse_kernel": {"sparse_kernel"},
            "boosting": {"boosting"}, "xgboost": {"xgboost"}, "evaluation": set(), "generic": set()}
SHARED_BY_THE_FAMILIES = {"evaluation", "generic"}
#: inside ``distributed``: what the planes are built from knows none of the parties
PARTS = {"protocol", "packing", "journal", "faults"}
PARTIES = {"broker", "client", "server", "sessions", "worker"}


def _refused(module: str, target: str) -> str | None:
    """Why ``module`` may not import ``target``, or None."""
    layer, other = _layer(module), _layer(target)
    if other not in MAY_IMPORT[layer]:
        return f"{layer} imports nothing of {other}"
    if "." not in target:  # a package's own names (its ``__init__.py``): the layers decide
        return None
    if layer == other == "models":
        mine, theirs = module.split(".")[1], target.split(".")[1]
        if theirs not in SHARED_BY_THE_FAMILIES | FAMILIES[mine]:
            return f"models.{mine} imports another family's module"
    if layer == other == "distributed" and module.split(".")[1] in PARTS and target.split(".")[1] in PARTIES:
        return f"{module} is a part; it imports no party ({', '.join(sorted(PARTIES))})"
    return None


def test_the_table_names_every_layer_and_every_module_of_the_models():
    assert {_layer(m) for m in MODULES} == set(MAY_IMPORT)
    assert {m.split(".")[1] for m in MODULES if _layer(m) == "models"} == set(FAMILIES)
    assert PARTS | PARTIES <= {m.split(".")[1] for m in MODULES if _layer(m) == "distributed"}
    assert len(EXCEPTIONS) == 1


@pytest.mark.parametrize("module", list(MODULES))
def test_a_modules_imports_are_the_ones_its_layer_allows(module):
    refused = {target: why for target in sorted(_imports(module)) if (why := _refused(module, target))}
    excepted = {target for (source, target) in EXCEPTIONS if source == module}
    assert set(refused) == excepted, {t: w for t, w in refused.items() if t not in excepted} or \
        f"an exception the code no longer needs: {excepted - set(refused)}"


def _cycles(graph: dict) -> list:
    """The strongly connected components of more than one module (Tarjan's, iterative)."""
    index, low, on_stack, stack, found, counter = {}, {}, set(), [], [], [0]
    for root in graph:
        if root in index:
            continue
        work = [(root, iter(sorted(graph[root])))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, targets = work[-1]
            for target in targets:
                if target not in index:
                    index[target] = low[target] = counter[0]
                    counter[0] += 1
                    stack.append(target)
                    on_stack.add(target)
                    work.append((target, iter(sorted(graph[target]))))
                    break
                if target in on_stack:
                    low[node] = min(low[node], index[target])
            else:
                work.pop()
                if work:
                    low[work[-1][0]] = min(low[work[-1][0]], low[node])
                if low[node] == index[node]:
                    component = []
                    while True:
                        member = stack.pop()
                        on_stack.discard(member)
                        component.append(member)
                        if member == node:
                            break
                    if len(component) > 1:
                        found.append(sorted(component))
    return found


def test_no_module_reaches_itself_through_its_imports():
    graph = {module: _imports(module) & set(MODULES) for module in MODULES}
    assert len(graph) == len(MODULES) > 40 and sum(map(len, graph.values())) > 100  # the walk found the package
    assert _cycles({"a": {"b"}, "b": {"c"}, "c": {"a"}, "d": {"a"}}) == [["a", "b", "c"]]
    assert _cycles(graph) == []
