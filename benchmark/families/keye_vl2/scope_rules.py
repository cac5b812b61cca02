"""The Keye-VL-2.0 family's op classes: what ``scope_reduce.py`` needs to know of
the model to turn a trace into seconds per class per program (the reading of
the trace itself is ``scope_reduce.py``'s and knows none of these names).

``models/lfm2_moe.py`` names its work with ``jax.named_scope``: ``embed``,
``layer{l}`` (``l`` the published index) with ``sparse_attention`` -- and
``proj``, ``rope``, ``indexer_proj``, ``indexer_scores``, ``select``, ``core``,
``indexer_loss`` inside it -- and ``moe/router|dispatch|experts|combine``,
``aux_loss``, ``head``, ``loss``, ``optimizer``.  jax wraps the outermost scope of
a transformed region (``jvp(layer2)``, ``transpose(jvp(layer2))``) and puts
``checkpoint``, ``rematted_computation`` and a loop's ``while/body`` between scopes
(a query block of the core is the body of a loop over its group's blocks and is
rematerialised on its own); the wrappers are stripped, and the first token under
``sparse_attention`` that is a scope of the layer's own decides (the vocabulary is docs/OBSERVABILITY.md's).

**The classes carry the accepted ``mel_*`` readers' names** (``mel_spans.py``
says why): ``full_core`` is the masked core, ``window_core`` the indexer.

- ``full_core``: under ``sparse_attention/core`` -- the mask from the scores and the
  thresholds, every head's score product, the softmax over the kept keys, the
  value product, with the scale and casts around them;
- ``window_core``: under ``sparse_attention/indexer_scores`` (the indexer's 16
  products a block, relu, weights, sum), ``select`` (the 32 counting passes of the
  bisection) and ``indexer_loss`` (the heads' mean share, the softmax of the scores
  over the kept keys, the KL term): the detail says which;
- ``attention_proj``: the rest of ``sparse_attention``: the q, k, v and output
  projections (``proj``), the q/k norm and rope by sections (``rope``), the
  indexer's three projections and its rope (``indexer_proj``);
- ``expert_mm``: under ``moe/experts``;
- ``moe_route``: the rest of ``moe``, and ``aux_loss``;
- ``head_loss``: ``embed``, ``head``, ``loss``;
- ``optimizer``: ``optimizer``;
- ``rest``: what carries a name but none of these scopes (the norms and residual
  adds of a layer, the batch gather, rng in ``lm_init``);
- ``unattributed``: no ``op_name`` at all.
"""

from __future__ import annotations

import re
from typing import Tuple

CLASSES = ("window_core", "full_core", "attention_proj", "expert_mm", "moe_route", "head_loss", "optimizer", "rest",
           "unattributed")
TRAIN, EVAL, INIT = "jit_lm_train_step", "jit_lm_eval", "jit_lm_init"
#: Base names of the jitted programs whose runs and HLO tables are read.
PROGRAMS = (TRAIN, EVAL, INIT)
#: (span and annotation kind, program): the fenced spans that hold each program's runs.
SPAN_PROGRAMS = (("train", TRAIN), ("eval", EVAL), ("init_params", INIT))
#: The attribute that tells the model's fenced device spans from other spans of their kind.
SPAN_ATTR = "individual"
#: The annotation that brackets one evaluator call, and its stat that counts the individuals.
CALL_ANNOTATION = ("cv_call", "n_real")
#: Details that only a program carrying the named scopes can show.
SCOPED_DETAILS = ("router", "dispatch", "experts", "combine", "core", "indexer_scores", "select", "indexer_loss")
#: The class of each sub-scope of ``sparse_attention`` that has one beside ``attention_proj``; the details of the rest.
SPARSE_CLASS = {"core": "full_core", "indexer_scores": "window_core", "select": "window_core", "indexer_loss": "window_core"}
PROJ_DETAILS = ("proj", "rope", "indexer_proj")
#: The accepted readers name a core by a layer type: which of this family's classes answers to each.
CORE_CLASS = {"full_attention": "full_core", "sliding_attention": "window_core"}

_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")
_LAYER = re.compile(r"^layer\d+$")
_BETWEEN = ("checkpoint", "rematted_computation")
_BY_SCOPE = (("embed", "head_loss"), ("head", "head_loss"), ("loss", "head_loss"), ("optimizer", "optimizer"))


def classify(op_name: str) -> Tuple[str, str]:
    """(class, detail) of one instruction from its ``op_name``; the detail is
    the ``moe`` or attention sub-scope, else the layer, else the scope itself."""
    if not op_name:
        return "unattributed", ""
    tokens = [t for t in _WRAPPER.sub("", op_name.rstrip(":")).split("/") if t not in _BETWEEN]
    layer = next((t for t in tokens if _LAYER.match(t)), "")
    if "moe" in tokens:
        inside = tokens[tokens.index("moe") + 1:]
        if inside[:1] == ["experts"]:
            return "expert_mm", "experts"
        return "moe_route", inside[0] if inside and inside[0] in SCOPED_DETAILS else "other"
    if "aux_loss" in tokens:
        return "moe_route", "aux_loss"
    if "sparse_attention" in tokens:
        # the first scope of the layer's own under it: a query block's body lies inside a loop (``while/body``)
        inside = next((t for t in tokens[tokens.index("sparse_attention") + 1:] if t in SPARSE_CLASS or t in PROJ_DETAILS), "other")
        return SPARSE_CLASS.get(inside, "attention_proj"), inside
    for scope, klass in _BY_SCOPE:
        if scope in tokens:
            return klass, layer or scope
    return "rest", layer or ("rng" if "threefry" in op_name or "random" in op_name else "other")
