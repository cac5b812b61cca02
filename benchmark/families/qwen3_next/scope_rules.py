"""The Qwen3-Next family's op classes: what ``scope_reduce.py`` needs to know of
the model to turn a trace into seconds per class per program (the reading of
the trace itself is ``scope_reduce.py``'s and knows none of these names).

``models/lfm2_moe.py`` names its work with ``jax.named_scope``: ``embed``,
``layer{l}`` (``l`` the published index) with the layer's type --
``linear_attention`` (``proj``, ``conv``, ``gates``, ``core``, ``norm_gate``
inside it) or ``full_attention`` (``proj``, ``rope``, ``core``, ``gate``) -- and
``moe/router|dispatch|experts|combine|shared`` and ``aux_loss``, ``head``,
``loss``, ``optimizer``.  jax wraps the outermost scope of a transformed region
(``jvp(layer2)``, ``transpose(jvp(layer2))``, ``checkpoint``,
``rematted_computation``); the wrappers are stripped and the scope tokens decide
(the vocabulary is docs/OBSERVABILITY.md's):

- ``delta_core``: under ``linear_attention/core`` -- the l2 norms of q and k, the
  chunks' decays and triangular systems, the scan over chunks that carries the
  state and its transpose;
- ``delta_conv``: under ``linear_attention/conv`` -- the causal depthwise
  convolution over the q, k and v columns and its SiLU;
- ``delta_proj``: the rest of ``linear_attention``: the in- and out-projections
  (``proj``), the write-strength and decay gates (``gates``), the per-head norm
  and output gate (``norm_gate``);
- ``full_core``: under ``full_attention/core`` -- the fused kernel's custom calls
  (forward, and the one backward kernel) or the blockwise core's products, with
  the scale, casts and transposes around them;
- ``attention_proj``: the rest of ``full_attention``: the q (with its gate), k, v
  and output projections (``proj``), the q/k norm and partial rope (``rope``), the
  sigmoid gate on the core's output (``gate``);
- ``shared_expert``: under ``moe/shared`` -- the shared expert's SwiGLU and its gate;
- ``expert_mm``: under ``moe/experts`` -- the three grouped products (the
  megablox kernels are custom calls that carry this scope) and the silu and
  masks between them;
- ``moe_route``: the rest of ``moe``: router product, softmax, top-k, the balance
  term (``aux_loss``), the sort, the gather of rows, the un-sort and the weighted sum;
- ``head_loss``: ``embed``, ``head``, ``loss``;
- ``optimizer``: ``optimizer``;
- ``rest``: what carries a name but none of these scopes (the norms and
  residual adds of a layer, the batch gather, rng in ``lm_init``);
- ``unattributed``: no ``op_name`` at all.
"""

from __future__ import annotations

import re
from typing import Tuple

CLASSES = ("delta_core", "delta_conv", "delta_proj", "full_core", "attention_proj", "shared_expert", "expert_mm",
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
SCOPED_DETAILS = ("router", "dispatch", "experts", "combine", "shared", "aux_loss", "core", "conv")
#: A mixer's scope -> (its sub-scopes that have a class of their own, the class of the rest, the rest's details).
MIXERS = {"linear_attention": ({"core": "delta_core", "conv": "delta_conv"}, "delta_proj", ("proj", "gates", "norm_gate")),
          "full_attention": ({"core": "full_core"}, "attention_proj", ("proj", "rope", "gate"))}

_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")
_LAYER = re.compile(r"^layer\d+$")
_BY_SCOPE = (("embed", "head_loss"), ("head", "head_loss"), ("loss", "head_loss"), ("optimizer", "optimizer"))


def classify(op_name: str) -> Tuple[str, str]:
    """(class, detail) of one instruction from its ``op_name``; the detail is
    the ``moe`` or mixer sub-scope, else the layer, else the scope itself."""
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
    if "aux_loss" in tokens:
        return "moe_route", "aux_loss"
    for scope, (own, rest, details) in MIXERS.items():
        if scope in tokens:
            inside = tokens[tokens.index(scope) + 1:]
            if inside[:1] and inside[0] in own:
                return own[inside[0]], inside[0]
            return rest, inside[0] if inside and inside[0] in details else "other"
    for scope, klass in _BY_SCOPE:
        if scope in tokens:
            return klass, layer or scope
    return "rest", layer or ("rng" if "threefry" in op_name or "random" in op_name else "other")
