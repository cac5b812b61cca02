"""The Nemotron-H family's op classes: what ``scope_reduce.py`` needs to know of
the model to turn a trace into seconds per class per program (the reading of
the trace itself is ``scope_reduce.py``'s and knows none of these names).

``models/lfm2_moe.py`` names its work with ``jax.named_scope``: ``embed``,
``layer{l}`` (``l`` the published index) with the block's one half --
``mamba2`` (``proj``, ``conv``, ``gates``, ``core``, ``norm_gate`` inside it),
``full_attention`` (``proj``, ``rope``, ``core``) or
``moe/router|latent_in|dispatch|experts|combine|latent_out|shared`` -- and
``head``, ``loss``, ``optimizer``, ``bias_update``.  jax wraps the outermost
scope of a transformed region (``jvp(layer2)``, ``transpose(jvp(layer2))``,
``checkpoint``, ``rematted_computation``); the wrappers are stripped and the
scope tokens decide (the vocabulary is docs/OBSERVABILITY.md's).

**The classes carry the names the accepted ``q3n_*`` readers ask for** (this
cell brings no metric of its own: ``q3n_spans.py``), so a scanning mixer's
classes are ``delta_*`` here too, though no delta rule runs:

- ``delta_core``: under ``mamba2/core`` -- the chunks' decays, ``C B'`` and its
  masked product, what a chunk adds to the state, the scan over chunks that
  carries it and its transpose, ``C S_in``, the skip;
- ``delta_conv``: under ``mamba2/conv`` -- the causal depthwise convolution over
  x, B and C, its bias and its SiLU;
- ``delta_proj``: the rest of ``mamba2``: the in- and out-projections
  (``proj``), the step and the decay rate (``gates``), the gate and the norm a
  group (``norm_gate``);
- ``full_core``: under ``full_attention/core`` -- the fused kernel's custom calls
  (forward, and the one backward kernel) at 16 query heads a key-value head, or
  the blockwise core's products, with the scale, casts and transposes around them;
- ``attention_proj``: the rest of ``full_attention``: the q, k, v and output
  projections (no norm, no rope);
- ``latent_proj``: under ``moe/latent_in`` and ``moe/latent_out`` -- the down- and
  up-projection around the routed experts (no accepted reader reads this class:
  it is on the traced run's ``info op_class`` lines);
- ``shared_expert``: under ``moe/shared`` -- the shared expert's two products and its relu2;
- ``expert_mm``: under ``moe/experts`` -- the two grouped products (the megablox
  kernels are custom calls that carry this scope) and the relu2 and masks between;
- ``moe_route``: the rest of ``moe``: router product, sigmoid, top-k, the scaling
  of the weights, the sort, the gather of rows, the un-sort and the weighted sum;
- ``head_loss``: ``embed``, ``head``, ``loss``;
- ``optimizer``: ``optimizer`` and ``bias_update``;
- ``rest``: what carries a name but none of these scopes (the norm and the
  residual add of a block, the batch gather, rng in ``lm_init``);
- ``unattributed``: no ``op_name`` at all.
"""

from __future__ import annotations

import re
from typing import Tuple

CLASSES = ("delta_core", "delta_conv", "delta_proj", "full_core", "attention_proj", "latent_proj", "shared_expert",
           "expert_mm", "moe_route", "head_loss", "optimizer", "rest", "unattributed")
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
SCOPED_DETAILS = ("router", "latent_in", "dispatch", "experts", "combine", "latent_out", "shared", "core", "conv")
#: A mixer's scope -> (its sub-scopes that have a class of their own, the class of the rest, the rest's details).
MIXERS = {"mamba2": ({"core": "delta_core", "conv": "delta_conv"}, "delta_proj", ("proj", "gates", "norm_gate")),
          "full_attention": ({"core": "full_core"}, "attention_proj", ("proj", "rope"))}
#: ``moe`` sub-scopes with a class of their own; the rest of ``moe`` is ``moe_route``.
MOE_CLASS = {"experts": "expert_mm", "shared": "shared_expert", "latent_in": "latent_proj", "latent_out": "latent_proj"}

_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")
_LAYER = re.compile(r"^layer\d+$")
_BY_SCOPE = (("embed", "head_loss"), ("head", "head_loss"), ("loss", "head_loss"), ("optimizer", "optimizer"),
             ("bias_update", "optimizer"))


def classify(op_name: str) -> Tuple[str, str]:
    """(class, detail) of one instruction from its ``op_name``; the detail is
    the ``moe`` or mixer sub-scope, else the layer, else the scope itself."""
    if not op_name:
        return "unattributed", ""
    tokens = _WRAPPER.sub("", op_name.rstrip(":")).split("/")
    layer = next((t for t in tokens if _LAYER.match(t)), "")
    if "moe" in tokens:
        inside = tokens[tokens.index("moe") + 1:]
        if inside[:1] and inside[0] in MOE_CLASS:
            return MOE_CLASS[inside[0]], inside[0]
        return "moe_route", inside[0] if inside and inside[0] in SCOPED_DETAILS else "other"
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
