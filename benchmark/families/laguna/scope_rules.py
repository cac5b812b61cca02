"""The Laguna family's op classes: what ``scope_reduce.py`` needs to know of
the model to turn a trace into seconds per class per program (the reading of
the trace itself is ``scope_reduce.py``'s and knows none of these names).

``models/lfm2_moe.py`` names its work with ``jax.named_scope``: ``embed``,
``layer{l}`` (``l`` the published index) with the layer's type --
``sliding_attention`` or ``full_attention``, and ``proj``, ``rope``, ``core``,
``gate`` inside it -- and ``dense_ffn`` or ``moe/router|dispatch|experts|combine|shared``,
``head``, ``loss``, ``optimizer``, ``bias_update``.  jax wraps the outermost scope
of a transformed region (``jvp(layer2)``, ``transpose(jvp(layer2))``,
``checkpoint``, ``rematted_computation``); the wrappers are stripped and the scope
tokens decide (the vocabulary is docs/OBSERVABILITY.md's):

- ``window_core``: under ``sliding_attention/core`` -- the fused kernel's custom
  calls under the window's mask at 8 query heads a key-value head (forward, and
  the one backward kernel) or the blockwise core's score and value products over
  the window's keys, with the scale, casts and transposes around them;
- ``full_core``: under ``full_attention/core`` -- the same under the causal mask
  at 6 query heads a key-value head;
- ``attention_gate``: under either attention scope's ``gate`` -- the float32
  product of the normed input with the head gates, the sigmoid and the broadcast
  product over a head's columns;
- ``attention_proj``: the rest of either attention scope: the q, k, v and output
  projections (``proj``) and the rope of its layer type (``rope``);
- ``dense_ffn``: under ``dense_ffn`` -- the leading layer's SwiGLU;
- ``shared_expert``: under ``moe/shared`` -- the shared expert's SwiGLU;
- ``expert_mm``: under ``moe/experts`` -- the three grouped products (the
  megablox kernels are custom calls that carry this scope) and the silu and
  masks between them;
- ``moe_route``: the rest of ``moe``: router product, sigmoid, top-k, the scaling
  of the weights, the sort, the gather of rows, the un-sort and the weighted sum;
- ``head_loss``: ``embed``, ``head``, ``loss``;
- ``optimizer``: ``optimizer`` and ``bias_update``;
- ``rest``: what carries a name but none of these scopes (the norms and
  residual adds of a layer, the batch gather, rng in ``lm_init``);
- ``unattributed``: no ``op_name`` at all.
"""

from __future__ import annotations

import re
from typing import Tuple

CLASSES = ("window_core", "full_core", "attention_proj", "attention_gate", "dense_ffn", "shared_expert", "expert_mm",
           "moe_route", "head_loss", "optimizer", "rest", "unattributed")
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
SCOPED_DETAILS = ("router", "dispatch", "experts", "combine", "shared", "core", "gate")
#: The core's class by the attention scope (the layer's type) it lies under.
CORE_CLASS = {"sliding_attention": "window_core", "full_attention": "full_core"}
#: An attention scope's sub-scopes with a class of their own beside its core's; the details of the rest.
OWN_CLASS = {"gate": "attention_gate"}
PROJ_DETAILS = ("proj", "rope")

_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")
_LAYER = re.compile(r"^layer\d+$")
_BY_SCOPE = (("dense_ffn", "dense_ffn"), ("embed", "head_loss"), ("head", "head_loss"), ("loss", "head_loss"),
             ("optimizer", "optimizer"), ("bias_update", "optimizer"))


def classify(op_name: str) -> Tuple[str, str]:
    """(class, detail) of one instruction from its ``op_name``; the detail is
    the ``moe`` or attention sub-scope, else the layer, else the scope itself."""
    if not op_name:
        return "unattributed", ""
    tokens = _WRAPPER.sub("", op_name.rstrip(":")).split("/")
    layer = next((t for t in tokens if _LAYER.match(t)), "")
    if "moe" in tokens:
        inside = tokens[tokens.index("moe") + 1:]
        if inside[:1] == ["experts"]:
            return "expert_mm", "experts"
        if inside[:1] == ["shared"]:
            return "shared_expert", "shared"
        return "moe_route", inside[0] if inside and inside[0] in SCOPED_DETAILS else "other"
    for scope, core in CORE_CLASS.items():
        if scope in tokens:
            inside = tokens[tokens.index(scope) + 1:]
            if inside[:1] == ["core"]:
                return core, "core"
            if inside[:1] and inside[0] in OWN_CLASS:
                return OWN_CLASS[inside[0]], inside[0]
            return "attention_proj", inside[0] if inside and inside[0] in PROJ_DETAILS else "other"
    for scope, klass in _BY_SCOPE:
        if scope in tokens:
            return klass, layer or scope
    return "rest", layer or ("rng" if "threefry" in op_name or "random" in op_name else "other")
