"""The comparison that decides ``correct`` for the ``keye_vl2`` family.

``program_side`` (set-up): the compiled ``lm_eval`` and ``lm_train_step`` of
this configuration -- the callables the window drives, through
``Lfm2MoeModel.compiled_programs`` (the routed family's model class; the
configuration makes it this architecture) -- on tokens and weights the benchmark
makes from the seed: the keys each query of each layer keeps on the first
held-out sequence (``lfm2_moe.selected_keys``: the programs' own operands,
thresholds and comparison, laid out as one array for this comparison alone),
the loss per token of one held-out batch, then ``check.steps`` train steps on
seeded batches.  Kept: the chosen sets (bits, on the host), the per-token loss,
each step's loss (the balance term and the indexers' losses included), each
step's rows per held expert and layer, the balance term, the indexers' losses
and the pairs kept that the state summed, the parameter change and AdamW's first
moment after the last step (both on the host) and the dropped assignments.

``after_window``: ``reference.py`` does the same from the same weights, float32
at ``highest``, selecting by ``lax.top_k``.  Compared, each against
``check.limits``:

- ``nll_gap``: loss per token of ``lm_eval`` against the reference's, root mean square over the batch's tokens,
  over the reference's mean;
- ``loss_gap``: |train loss - the reference's| over the reference's, worse step (the loss that was differentiated:
  cross-entropy plus ``aux_alpha`` times the balance term plus the indexers' losses);
- ``aux_gap``: |balance term - the reference's| over the reference's, the steps together;
- ``indexer_loss_gap``: |the indexers' losses - the reference's| over the reference's, layers and steps together
  (the KL term alone: a wrong target, a target not averaged over the heads, a softmax over the wrong keys);
- ``selection_gap``: the share of (query, key) choices on which program and reference differ: pairs kept by one
  side alone, all layers, over the pairs the reference keeps (a wrong score, rank, threshold or causal rule shows
  here before it shows anywhere else; two sound sides differ on the keys nearest the threshold);
- ``selected_pairs``: |pairs the train steps kept (counted on the device, all layers and steps) - the arithmetic's|,
  in pairs: a query with ``t + 1 <= topk`` keys keeps them all, every other ``topk``:
  ``topk (topk + 1) / 2 + (T - topk) topk`` a sequence and layer when no two scores tie at a threshold (a tie keeps
  both: scores of exactly zero, where every indexer head's relu is shut, can tie);
- ``dparam_gap.<group>``: ||change_program - change_reference|| / ||change_reference|| over each group of leaves
  (experts, router, attention, indexer, embedding, head, norms), each against its own limit: the indexer's three
  matrices apart from the trunk's, for they learn from another loss;
- ``moment_gap.<group>``: the same ratio of AdamW's first moment after the last step, which is linear in the
  gradients of every step;
- ``dnorm_gap``: | ||change_program|| - ||change_reference|| | / ||change_reference||, worst group;
- ``load_gap``: largest |rows - the reference's rows| of a held expert in a layer and step, over the mean rows a
  held expert gets in that step;
- ``dropped_assignments``: 0.

With ``control`` (tests and ``tests/kvl_readings.py``) the reference computed in
that lower precision is put in the program's place and the same numbers are
returned for it.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import reference

GROUPS = ("experts", "router", "attention", "indexer", "embedding", "head", "norms")


def group_of(path: str) -> str:
    """The group of the leaf at ``path`` (``jax.tree_util.keystr``)."""
    if "norm" in path:
        return "norms"
    if "moe" in path:
        return "router" if "router" in path else "experts"
    if "indexer" in path:
        return "indexer"
    if "attn" in path:
        return "attention"
    return "head" if "head" in path else "embedding"


def _leaves(tree) -> List[Tuple[str, Any]]:
    import jax

    return [(jax.tree_util.keystr(p), v) for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


def expected_pairs(m: Dict[str, Any], length: int) -> int:
    """The pairs one layer keeps on one sequence when no two scores tie at a threshold."""
    top = min(m["topk"], length)
    return top * (top + 1) // 2 + (length - top) * top


def check_inputs(ctx) -> Dict[str, Any]:
    """What both sides start from, all from the seed: weights (the router's
    ``check.router_gain`` times wider, so that its softmax is far from uniform;
    the embedding at ``check.embed_std`` and the residual writers at
    ``check.out_std`` where the configuration gives them), the batches (rows of
    the seed's own tokens), the recipe."""
    check, cfg = ctx.config["check"], ctx.config
    rng = np.random.default_rng([ctx.seed, 0xC0DE])
    n_train = ctx.check_x.shape[0] - cfg["run"]["eval_sequences"]
    rows = rng.permutation(n_train)[:cfg["train_steps"] * cfg["run"]["batch_sequences"]]
    return {"weights": reference.seeded_weights(ctx.model, ctx.seed, check["weight_std"], check.get("router_gain", 2.0),
                                                check.get("embed_std"), check.get("out_std")),
            "train_rows": np.resize(rows, (cfg["train_steps"], cfg["run"]["batch_sequences"])).astype(np.int32),
            "eval_rows": np.arange(n_train, n_train + cfg["run"]["batch_sequences"], dtype=np.int32),
            "genes": dict(check["genes"]), "steps": int(check["steps"])}


def program_side(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from gentun_tpu.models import lfm2_moe
    from gentun_tpu.models.lfm2_moe import Lfm2MoeModel, gene_vector

    t0 = time.monotonic()
    inputs = check_inputs(ctx)
    programs = Lfm2MoeModel.compiled_programs(ctx.x, **ctx.params)
    x, y = jnp.asarray(ctx.check_x), jnp.asarray(ctx.check_y)
    state = programs.init(jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))  # the state's form; its weights go
    state = {**state, "params": jax.device_put(inputs["weights"])}
    first = jnp.asarray(inputs["eval_rows"][:1])
    kept = jax.jit(lambda params, bias, rows: lfm2_moe.selected_keys(programs.config, params, bias, x[rows])[:, 0])(
        state["params"], state["bias"], first)
    chosen = np.packbits(np.asarray(kept), axis=-1)  # (layers, length, length / 8) on the host
    del kept
    nll = np.asarray(programs.eval(state["params"], state["bias"], x, y, jnp.asarray(inputs["eval_rows"])))
    genes, rows = jnp.asarray(gene_vector(inputs["genes"], programs.config.gene_names)), jnp.asarray(inputs["train_rows"])
    losses, loads = [], []
    for step in range(inputs["steps"]):
        state, loss, held = programs.train_step(state, x, y, rows, genes, np.int32(step))
        losses.append(float(loss))
        loads.append(np.asarray(held))
    change = {path: np.asarray(after) - before
              for (path, after), (_, before) in zip(_leaves(state["params"]), _leaves(inputs["weights"]))}
    out = {"inputs": inputs, "nll": nll, "losses": losses, "loads": loads, "change": change,
           "moment": {path: np.asarray(m) for path, m in _leaves(state["m"])}, "chosen": chosen,
           "balance": float(state["aux_loss"]), "indexer_loss": float(state["indexer_loss"]),
           "selected": int(np.asarray(state["selected_pairs"], np.int64).sum()), "dropped": int(state["dropped"])}
    del state
    print(f"info keye_vl2 program_side: {time.monotonic() - t0:.1f} s; losses {losses}; balance terms summed "
          f"{out['balance']}; indexers' losses summed {out['indexer_loss']}; pairs kept {out['selected']}; dropped "
          f"{out['dropped']}")
    return out


def reference_side(ctx, inputs: Dict[str, Any], control: Optional[str] = None) -> Dict[str, Any]:
    """The same numbers from ``reference.py`` (``control``: in that lower precision)."""
    m = ctx.model
    lo, hi = m["held_experts"]
    x, y = ctx.check_x, ctx.check_y
    chosen = np.packbits(reference.selections(m, inputs["weights"], x[inputs["eval_rows"][0]], control), axis=-1)
    nll = reference.eval_token_loss(m, inputs["weights"], x[inputs["eval_rows"]], y[inputs["eval_rows"]], control)
    batches = [(x[r], y[r]) for r in inputs["train_rows"][:inputs["steps"]]]
    trained = reference.train(m, inputs["weights"], batches, inputs["genes"], control)
    change = {path: np.asarray(after) - before
              for (path, after), (_, before) in zip(_leaves(trained["weights"]), _leaves(inputs["weights"]))}
    sequences = sum(len(xb) for xb, _ in batches)
    return {"nll": nll, "losses": trained["losses"], "loads": [l[:, lo:hi] for l in trained["loads"]],
            "change": change, "moment": dict(_leaves(trained["moment"])), "chosen": chosen,
            "balance": sum(trained["balances"]), "indexer_loss": sum(trained["indexer_losses"]),
            "selected": int(sum(int(np.asarray(s, np.int64).sum()) for s in trained["selected"])), "dropped": 0,
            "expected_pairs": expected_pairs(m, x.shape[1]) * m["num_hidden_layers"] * sequences}


def _sum_of_squares(v: np.ndarray, block: int = 1 << 18) -> float:
    """Of a float32 leaf of up to 39 M elements: float32 dot products of short blocks, added up in float64."""
    return sum(float(np.dot(v[i:i + block], v[i:i + block])) for i in range(0, v.size, block))


def _by_group(side: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, Tuple[float, float]]:
    """Per group of leaves: (||side - ref|| / ||ref||, | ||side|| - ||ref|| | / ||ref||)."""
    sums = {g: np.zeros(3) for g in GROUPS}
    for path, r in ref.items():
        a, r = np.ravel(side[path]), np.ravel(r)
        sums[group_of(path)] += (_sum_of_squares(a - r), _sum_of_squares(a), _sum_of_squares(r))
    return {g: (float(np.sqrt(d / r)), float(abs(np.sqrt(a) - np.sqrt(r)) / np.sqrt(r)))
            for g, (d, a, r) in sums.items() if r > 0}


def _bits(a: np.ndarray) -> int:
    """The bits set in ``a`` (uint8, up to 134 MB): unpacked a part at a time."""
    return sum(int(np.unpackbits(part).sum(dtype=np.int64)) for part in np.array_split(a.ravel(), 64))


def compare(side: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers of the module docstring: ``side`` (the program, or the control) against the reference."""
    change, moment = _by_group(side["change"], ref["change"]), _by_group(side["moment"], ref["moment"])
    nll = (side["nll"] - ref["nll"]).astype(np.float64)
    apart, kept = _bits(side["chosen"] ^ ref["chosen"]), _bits(ref["chosen"])
    print(f"info keye_vl2 nll worst token over the mean: {np.abs(nll).max() / ref['nll'].mean():.5f}; chosen sets: "
          f"{apart} pairs kept by one side alone of {kept} the reference keeps; pairs the steps kept {side['selected']} "
          f"against {ref['expected_pairs']} by arithmetic; dnorm_gap by group:", {g: round(v[1], 6) for g, v in change.items()})
    return {
        "nll_gap": float(np.sqrt(np.mean(nll ** 2)) / ref["nll"].mean()),
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(side["losses"], ref["losses"])),
        "aux_gap": abs(side["balance"] - ref["balance"]) / abs(ref["balance"]),
        "indexer_loss_gap": abs(side["indexer_loss"] - ref["indexer_loss"]) / abs(ref["indexer_loss"]),
        "selection_gap": apart / kept,
        "selected_pairs": float(abs(side["selected"] - ref["expected_pairs"])),
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
    import jax

    t0 = time.monotonic()
    jax.clear_caches()  # the window's programs go: loaded, this runtime keeps their scratch reserved beside the reference
    ref = reference_side(ctx, prog["inputs"])
    sound = compare(prog, ref)
    limits = flat_limits(ctx.config["check"]["limits"])
    checks = [{"name": k, "value": v, "limit": limits[k], "ok": bool(v <= limits[k])} for k, v in sound.items()]
    print(f"info keye_vl2 reference: {time.monotonic() - t0:.1f} s; losses {ref['losses']}; indexers' losses summed "
          f"{ref['indexer_loss']}; rows per held expert, first step, first layer, program {prog['loads'][0][0].tolist()} "
          f"reference {ref['loads'][0][0].tolist()}")
    return checks, (compare(reference_side(ctx, prog["inputs"], control), ref) if control else None)
