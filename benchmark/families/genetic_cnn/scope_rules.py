"""The Genetic-CNN's op classes: what ``scope_reduce.py`` needs to know of a
model to turn a trace into seconds per class per program.  The trace reading
itself (protobuf, matching events to modules, self time, the fusion vote,
``per_individual``) is ``scope_reduce.py``'s and knows none of these names;
the family's readers under ``layer_metrics/`` hand it this module.

Classes (the vocabulary is docs/OBSERVABILITY.md's): an instruction whose
``op_name`` passes through the model (``MaskedGeneticCnn``, under ``jvp(``
forward, ``transpose(jvp(`` backward, bare in the eval program) is
``conv_fwd``/``conv_bwd`` under a conv module (``stage*_entry|node*|exit``),
``head`` under ``head``/``Dense_*``/``Dropout_*``, else ``glue``: the
``stage{s}/mask_sum|gate|merge|pool`` scopes, and what has neither module nor
scope (relu, casts).  Outside the model it is ``rest`` (``loss``, ``optimizer``,
``gather``, ``score``, rng, loop bookkeeping).  Without ``op_name``:
``unattributed``.  Programs served from a compile-cache entry written before
the scopes existed carry the older names; the same rules then give the same
five classes, only the detail column cannot tell a mask sum from a pool.
"""

from __future__ import annotations

import re
from typing import Tuple

#: Every class ``classify`` can answer; ties in a fusion's vote go to the earlier.
CLASSES = ("conv_fwd", "conv_bwd", "glue", "head", "rest", "unattributed")
TRAIN, EVAL = "jit_train_segment", "jit_eval_fold"  # the family's two programs
#: Base names of the jitted programs whose runs and HLO tables are read.
PROGRAMS = (TRAIN, EVAL)
#: (span and annotation kind, program): the fenced spans that hold each program's runs.
SPAN_PROGRAMS = (("train", TRAIN), ("eval", EVAL))
#: The attribute that tells a program's fenced device span from other spans of its kind.
SPAN_ATTR = "fold"
#: The annotation that brackets one evaluator call, and its stat that counts the individuals.
CALL_ANNOTATION = ("cv_call", "n_real")

MODEL = "MaskedGeneticCnn"
CONV_MODULE = re.compile(r"^stage\d+_(entry|node\d+|exit)$")
HEAD_MODULE = re.compile(r"^(head|Dense_\d+|Dropout_\d+)$")
GLUE_SCOPES = ("mask_sum", "gate", "merge", "pool")
REST_SCOPES = re.compile(r"\b(loss|optimizer|gather|score)\b")
#: Details that only a program carrying the named scopes can show.
SCOPED_DETAILS = GLUE_SCOPES


def classify(op_name: str) -> Tuple[str, str]:
    """(class, detail) of one instruction from its ``op_name``."""
    if not op_name:
        return "unattributed", ""
    parts = op_name.rstrip(":").split("/")
    at = next((i for i, p in enumerate(parts) if MODEL in p), None)
    if at is None:
        scope = REST_SCOPES.search(op_name)
        return "rest", scope.group(1) if scope else ("rng" if "threefry" in op_name else "other")
    backward = parts[at].startswith("transpose(")
    inside = parts[at + 1:]
    for i, part in enumerate(inside):
        if CONV_MODULE.match(part):
            return ("conv_bwd" if backward else "conv_fwd"), part
        if HEAD_MODULE.match(part):
            return "head", "head"
        if re.match(r"^stage\d+$", part) and inside[i + 1:i + 2] and inside[i + 1] in GLUE_SCOPES:
            return "glue", inside[i + 1]
    return "glue", "no_scope"
