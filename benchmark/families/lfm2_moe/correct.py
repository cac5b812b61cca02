"""The comparison that decides ``correct`` for the ``lfm2_moe`` family.

``program_side`` (set-up): the compiled ``lm_eval`` and ``lm_train_step`` of
this configuration -- the callables the window drives, through
``Lfm2MoeModel.compiled_programs`` and no private name -- on tokens, weights
and a router bias the benchmark makes from the seed: the loss per token of one
held-out batch, then ``check.steps`` train steps on seeded batches.  Kept: the
per-token loss, each step's loss, each step's rows per held expert and routed
layer, the parameter change and AdamW's first moment after the last step (both
on the host, so that the window's memory is the window's own) and the dropped
assignments.

``after_window``: ``reference.py`` does the same from the same weights,
float32 at ``highest``.  Compared, each against ``check.limits``:

- ``nll_gap``: loss per token of ``lm_eval`` against the reference's, root mean square over the batch's 16,384
  tokens, over the reference's mean (the worst token is printed beside it: it reads the size of one flipped
  expert choice, 0.03-0.05, in bfloat16 and in fp8 alike, so it cannot tell them apart);
- ``loss_gap``: |train loss - the reference's| over the reference's, worse step;
- ``dparam_gap.<group>``: ||change_program - change_reference|| / ||change_reference|| over each group of
  leaves (experts, router, operators, dense_ffn, embedding, norms), each against its own limit: under Adam
  the first steps move every element by about the learning rate, so this reads the share of elements whose
  small gradient changed sign, and that share differs by group (the router's is three times the dense
  feed-forward's);
- ``moment_gap.<group>``: the same ratio of AdamW's first moment after the last step, which is linear in
  the gradients of every step: a gradient wrong in size shows here, where its sign alone would not;
- ``dnorm_gap``: | ||change_program|| - ||change_reference|| | / ||change_reference||, worst group: the size
  of the update (learning rate, warm-up, bias correction), which sign flips leave alone;
- ``load_gap``: largest |rows - the reference's rows| of a held expert in a routed layer and step, over the
  mean rows a held expert gets in that step (an expert with a handful of rows would make a ratio to its own
  rows noise): near-ties may flip, a wrong routing rule does not hide;
- ``dropped_assignments``: 0.

With ``control`` (tests and ``tests/readings.py``) the reference computed in
that lower precision is put in the program's place and the same numbers are
returned for it.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import reference

GROUPS = ("experts", "router", "operators", "dense_ffn", "embedding", "norms")


def group_of(path: str) -> str:
    if "norm" in path:
        return "norms"
    if "moe" in path:
        return "router" if "router" in path else "experts"
    if "dense" in path:
        return "dense_ffn"
    return "operators" if ("conv" in path or "attn" in path) else "embedding"


def _leaves(tree) -> List[Tuple[str, Any]]:
    import jax

    return [(jax.tree_util.keystr(p), v) for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


def check_inputs(ctx) -> Dict[str, Any]:
    """What both sides start from, all from the seed: weights, a router bias
    large enough that ignoring it changes the choice, the batches (rows of the
    seed's own tokens, ``ctx.check_x``: the window's come from the
    configuration's ``window_seed``, ``family.make_inputs``), the recipe."""
    check, cfg = ctx.config["check"], ctx.config
    rng = np.random.default_rng([ctx.seed, 0xC0DE])
    n_routed = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    n_train = ctx.check_x.shape[0] - cfg["run"]["eval_sequences"]
    rows = rng.permutation(n_train)[:cfg["train_steps"] * cfg["run"]["batch_sequences"]]
    return {"weights": reference.seeded_weights(ctx.model, ctx.seed, check["weight_std"]),
            "bias": (check["bias_std"] * rng.standard_normal((n_routed, cfg["num_experts"]))).astype(np.float32),
            "train_rows": np.resize(rows, (cfg["train_steps"], cfg["run"]["batch_sequences"])).astype(np.int32),
            "eval_rows": np.arange(n_train, n_train + cfg["run"]["batch_sequences"], dtype=np.int32),
            "genes": dict(check["genes"]), "steps": int(check["steps"])}


def program_side(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from gentun_tpu.models.lfm2_moe import Lfm2MoeModel, gene_vector

    t0 = time.monotonic()
    inputs = check_inputs(ctx)
    programs = Lfm2MoeModel.compiled_programs(ctx.x, **ctx.params)
    x, y = jnp.asarray(ctx.check_x), jnp.asarray(ctx.check_y)
    state = programs.init(jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))  # the state's form; its weights go
    state = {**state, "params": jax.device_put(inputs["weights"]), "bias": jnp.asarray(inputs["bias"])}
    nll = np.asarray(programs.eval(state["params"], state["bias"], x, y, jnp.asarray(inputs["eval_rows"])))
    genes, rows = jnp.asarray(gene_vector(inputs["genes"])), jnp.asarray(inputs["train_rows"])
    losses, loads = [], []
    for step in range(inputs["steps"]):
        state, loss, held = programs.train_step(state, x, y, rows, genes, np.int32(step))
        losses.append(float(loss))
        loads.append(np.asarray(held))
    change = {path: np.asarray(after) - before
              for (path, after), (_, before) in zip(_leaves(state["params"]), _leaves(inputs["weights"]))}
    out = {"inputs": inputs, "nll": nll, "losses": losses, "loads": loads, "change": change,
           "moment": {path: np.asarray(m) for path, m in _leaves(state["m"])},
           "bias": np.asarray(state["bias"]), "dropped": int(state["dropped"])}
    del state
    print(f"info lfm2_moe program_side: {time.monotonic() - t0:.1f} s; losses {losses}; dropped {out['dropped']}")
    return out


def reference_side(ctx, inputs: Dict[str, Any], control: Optional[str] = None) -> Dict[str, Any]:
    """The same numbers from ``reference.py`` (``control``: in that lower precision)."""
    import jax.numpy as jnp

    m = ctx.model
    lo, hi = m["held_experts"]
    x, y = ctx.check_x, ctx.check_y
    nll = reference.eval_token_loss(m, inputs["weights"], jnp.asarray(inputs["bias"]), x[inputs["eval_rows"]],
                                    y[inputs["eval_rows"]], control)
    batches = [(x[r], y[r]) for r in inputs["train_rows"][:inputs["steps"]]]
    trained = reference.train(m, inputs["weights"], batches, inputs["genes"], control, bias=inputs["bias"])
    change = {path: np.asarray(after) - before
              for (path, after), (_, before) in zip(_leaves(trained["weights"]), _leaves(inputs["weights"]))}
    return {"nll": nll, "losses": trained["losses"], "loads": [l[:, lo:hi] for l in trained["loads"]],
            "change": change, "moment": dict(_leaves(trained["moment"])), "bias": np.asarray(trained["bias"]),
            "dropped": 0}


def _sum_of_squares(v: np.ndarray, block: int = 1 << 18) -> float:
    """Of a float32 leaf of up to 75 M elements: float32 dot products of short blocks, added up in float64."""
    return sum(float(np.dot(v[i:i + block], v[i:i + block])) for i in range(0, v.size, block))


def _by_group(side: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, Tuple[float, float]]:
    """Per group of leaves: (||side - ref|| / ||ref||, | ||side|| - ||ref|| | / ||ref||)."""
    sums = {g: np.zeros(3) for g in GROUPS}
    for path, r in ref.items():
        a, r = np.ravel(side[path]), np.ravel(r)
        sums[group_of(path)] += (_sum_of_squares(a - r), _sum_of_squares(a), _sum_of_squares(r))
    return {g: (float(np.sqrt(d / r)), float(abs(np.sqrt(a) - np.sqrt(r)) / np.sqrt(r)))
            for g, (d, a, r) in sums.items() if r > 0}


def compare(side: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers of the module docstring: ``side`` (the program, or the control) against the reference."""
    change, moment = _by_group(side["change"], ref["change"]), _by_group(side["moment"], ref["moment"])
    nll = (side["nll"] - ref["nll"]).astype(np.float64)
    print(f"info lfm2_moe nll worst token over the mean: {np.abs(nll).max() / ref['nll'].mean():.5f}; "
          "dnorm_gap by group:", {g: round(v[1], 6) for g, v in change.items()})
    return {
        "nll_gap": float(np.sqrt(np.mean(nll ** 2)) / ref["nll"].mean()),
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(side["losses"], ref["losses"])),
        **{f"dparam_gap.{g}": v[0] for g, v in change.items()},
        **{f"moment_gap.{g}": v[0] for g, v in moment.items()},
        "dnorm_gap": max(v[1] for v in change.values()),
        "load_gap": max(float(np.max(np.abs(a.astype(np.int64) - b)) / max(b.mean(), 1.0))
                        for a, b in zip(side["loads"], ref["loads"])),
        "dropped_assignments": float(side["dropped"]),
    }


def flat_limits(limits: Dict[str, Any]) -> Dict[str, float]:
    """``check.limits`` with a number's per-group limits under ``<number>.<group>``."""
    return {name if group is None else f"{name}.{group}": value
            for name, entry in limits.items()
            for group, value in (entry.items() if isinstance(entry, dict) else [(None, entry)])}


def after_window(ctx, prog: Dict[str, Any], control: Optional[str] = None
                 ) -> Tuple[List[Dict[str, Any]], Optional[Dict[str, float]]]:
    """Each number compared, beside its limit; and, with ``control``, what the
    reference computed in that lower precision reads in the program's place."""
    t0 = time.monotonic()
    ref = reference_side(ctx, prog["inputs"])
    sound = compare(prog, ref)
    limits = flat_limits(ctx.config["check"]["limits"])
    checks = [{"name": k, "value": v, "limit": limits[k], "ok": bool(v <= limits[k])} for k, v in sound.items()]
    print(f"info lfm2_moe reference: {time.monotonic() - t0:.1f} s; losses {ref['losses']}; rows per held expert, "
          f"first step, program {prog['loads'][0].tolist()} reference {ref['loads'][0].tolist()}")
    return checks, (compare(reference_side(ctx, prog["inputs"], control), ref) if control else None)
