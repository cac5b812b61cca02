"""What ``correct`` compares: the system's compiled programs against the reference.

Two halves, so the reference never runs inside set-up or the window:

``program_side`` (set-up, after the warm-up call): hands seeded weights made
by the benchmark to the very callables the window drives -- the lru-cached
jitted ``train_pop``/``eval_pop`` of ``models.cnn._fold_segment_fns`` at the
configuration's own static key, step count, batch and dataset shape, at every
population width the cell's window runs (``check.program_widths``: the deep
cell's 16-wide chunks and its 2-wide last chunk), so no new program is
compiled and the executables are the window's -- and keeps, for the sampled
slots, the parameter change and the optimizer's momentum trace.

``after_window`` (once the window has closed and the peak memory has been
read): trains the sampled genomes from the same weights on the same rows with
the same dropout masks in plain float32 (``reference.py``) and compares the
train program's numbers with it.  Then the validation pass: nineteen steps
leave a network near chance (the deep cell's fitness is 0.013 for 100
classes), where an accuracy cannot tell a right pass from a wrong one, so the
reference fits a last projection to its own hidden rows of the training part
(``reference.fit_head``).  And on the data as it is such a network is right
on nearly every row by a wide margin, where no rounding shows; so half of
the check's validation rows are blends of two images, ``w a + (1 - w) b``
with ``w`` between 0.3 and 0.7: rows that cross the boundary between two
classes at an even density.  The network the reference trained, with that
head, goes to the system's compiled ``eval_pop`` twice: over the plain half
with the true labels (``acc_gap``, beside the reference's own accuracy, which
has to be far from chance: ``eval_ref_acc``), and over the blended half with
the class the *reference* puts first as the label, so that what ``eval_pop``
returns is the share of rows on which it agrees with the reference
(``eval_flip`` = 1 - that share).  ``eval_flip`` is the number a lower
precision inside the compiled validation program moves.  ``logit_gap``
compares the system's module under the population ``vmap`` in the configured
compute dtype with the reference's logits on one batch; the compiled step
returns no logits, so that one number is read from a forward program built
here, after the window.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import reference


def _leaf_norms(tree) -> Dict[str, float]:
    return {f"{layer}/{name}": float(np.linalg.norm(np.asarray(leaf, np.float64)))
            for layer, leaves in tree.items() for name, leaf in leaves.items()}


def worst_leaf_gap(program: Dict[str, float], ref: Dict[str, float]) -> float:
    """Largest |program norm - reference norm| over the reference's norm of
    that leaf or of the median leaf, whichever is larger (some leaves --
    nodes the genome drops -- do not move at all)."""
    floor = float(np.median(list(ref.values())))
    return max(abs(program[k] - ref[k]) / max(ref[k], floor, 1e-30) for k in ref)


def _sub(a, b):
    return {layer: {n: np.asarray(a[layer][n], np.float64) - np.asarray(b[layer][n], np.float64)
                    for n in a[layer]} for layer in a}


def _slot(tree, slot: int):
    return {layer: {n: np.asarray(a[slot]) for n, a in leaves.items()} for layer, leaves in tree.items()}


def program_side(ctx) -> Dict[str, Any]:
    """Drive the system's compiled train step on seeded weights at every
    width the window runs; numbers only, plus what ``after_window`` needs."""
    import jax.numpy as jnp

    from gentun_tpu.models import cnn
    from gentun_tpu.ops.dag import stack_genome_masks

    config, check = ctx.config, ctx.config["check"]
    model_cfg, data = config["model"], config["data"]
    nodes = tuple(model_cfg["nodes"])
    rng = np.random.default_rng([ctx.seed, 0xC0])

    cfg = cnn._normalize_config(ctx.x, ctx.y, dict(ctx.params))
    n, kfold = len(ctx.x), cfg["kfold"]
    fold_size = n // kfold
    n_train = fold_size * kfold - fold_size
    batch = min(cfg["batch_size"], n_train)
    steps = sum(cfg["epochs"]) * max(n_train // batch, 1)
    eval_bs, n_val_padded = cnn._eval_batch_size(batch, fold_size)
    widths = [int(w) for w in check["program_widths"]]
    if cnn.auto_mesh(pop_size=widths[0]) is not None:
        raise SystemExit("correct.py drives the one-device programs only; this "
                         "process sees a mesh (see benchmark/README.md)")
    init_pop, train_pop, eval_pop = cnn._fold_segment_fns(
        *cnn._static_key(cfg, batch, n_train, n_val_padded, eval_bs))

    # The sample: the genome with an empty stage (the first of the file) and
    # others drawn from the seed; each with weights, a dropout key and rows
    # of its own, the same at every width.
    listed = [dict(g) for g in check["reference_genomes"]]
    picks = [0] + sorted(1 + int(i) for i in rng.choice(len(listed) - 1, size=int(check["sampled"]) - 1,
                                                         replace=False))
    shape_args = (nodes, model_cfg["kernels_per_layer"], model_cfg["dense_units"],
                  data["n_classes"], data["input_shape"])
    seeds = [int(s) for s in rng.integers(0, 2**31 - 1, size=len(picks) + 1)]
    sampled = [{"genome": listed[i], "weights_seed": s,
                "key": np.asarray(rng.integers(0, 2**32, size=2, dtype=np.uint64), np.uint32)}
               for i, s in zip(picks, seeds)]
    filler = reference.seeded_params(seeds[-1], *shape_args)
    batch_idx = rng.integers(0, n, size=(steps, batch)).astype(np.int32)
    order = rng.permutation(n)
    val_rows, fit_rows = order[:fold_size].astype(np.int32), order[fold_size:].astype(np.int32)
    x_full, y_full = jnp.asarray(ctx.x, jnp.float32), jnp.asarray(ctx.y, jnp.int32)

    per_width = []
    for width in widths:
        members = sampled[:width]
        slots = [int(s) for s in rng.choice(width, size=len(members), replace=False)]
        fill = [ctx.pool[i % len(ctx.pool)] for i in rng.permutation(max(len(ctx.pool), width))[:width]]
        genomes = list(fill)
        keys = np.asarray(rng.integers(0, 2**32, size=(width, 2), dtype=np.uint64), np.uint32)
        for slot, s in zip(slots, members):
            genomes[slot], keys[slot] = s["genome"], s["key"]
        masks = [{k: jnp.asarray(v) for k, v in stage.items()}
                 for stage in stack_genome_masks(genomes, nodes)]

        def stacked(trees, width=width, slots=slots):
            """A ``width``-wide parameter tree: ``trees`` in the sampled
            slots, the filler's weights in every other."""
            def leaf(layer, name):
                a = jnp.broadcast_to(jnp.asarray(filler[layer][name]), (width, *filler[layer][name].shape))
                for slot, tree in zip(slots, trees):
                    a = a.at[slot].set(jnp.asarray(tree[layer][name]))
                return a

            return {layer: {name: leaf(layer, name) for name in leaves}
                    for layer, leaves in filler.items()}

        # The compiled step the window drives, one fold's worth.  The carries
        # are donated, so the step gets trees of its own.
        start = [reference.seeded_params(s["weights_seed"], *shape_args) for s in members]
        params = stacked(start)
        p, opt, _ = train_pop(params, init_pop(params), masks, x_full, y_full,
                              jnp.asarray(batch_idx), jnp.asarray(keys))
        trace = next(s.trace for s in opt if hasattr(s, "trace"))
        per_width.append({"width": width, "slots": slots, "masks": masks, "stacked": stacked,
                          "dparam_norms": [_leaf_norms(_sub(_slot(p, slot), tree))
                                           for slot, tree in zip(slots, start)],
                          "trace_norms": [_leaf_norms(_slot(trace, slot)) for slot in slots]})
        del p, opt, params, trace
    # The check's validation rows: the second half are blends of two images.
    plain, blended = val_rows[: fold_size // 2], val_rows[fold_size // 2:]
    weight = rng.uniform(0.3, 0.7, size=len(blended)).astype(np.float32)[:, None, None, None]
    x_check = np.array(ctx.x, np.float32)
    x_check[blended] = weight * x_check[blended] + (1.0 - weight) * x_check[rng.permutation(blended)]
    val_idx = np.concatenate([val_rows, np.full(n_val_padded - fold_size, val_rows[0], np.int32)])
    on = lambda lo, hi: np.concatenate([np.zeros(lo, np.float32), np.ones(hi - lo, np.float32),
                                        np.zeros(n_val_padded - hi, np.float32)])
    return {"sampled": sampled, "widths": per_width, "eval_pop": eval_pop, "cfg": cfg,
            "batch_idx": batch_idx, "val_rows": val_rows, "fit_rows": fit_rows,
            "x_check": x_check, "val_idx": val_idx, "plain": plain, "blended": blended,
            "plain_weight": on(0, len(plain)), "blended_weight": on(len(plain), fold_size),
            "shape_args": shape_args,
            "schedule": {"learning_rate": cfg["learning_rate"], "momentum": cfg["momentum"],
                         "dropout_rate": cfg["dropout_rate"],
                         "epoch_steps": [e * max(n_train // batch, 1) for e in cfg["epochs"]]}}


def _reference_numbers(ctx, prog, s, quantize: Optional[str]) -> Dict[str, Any]:
    """One sampled genome through the reference (and, for the control, through
    the reference in the lower precision, in the program's place)."""
    import jax.numpy as jnp

    check = ctx.config["check"]
    nodes = tuple(ctx.config["model"]["nodes"])
    x_full, y_full = jnp.asarray(ctx.x, jnp.float32), jnp.asarray(ctx.y, jnp.int32)
    sched = prog["schedule"]
    tree = reference.seeded_params(s["weights_seed"], *prog["shape_args"])
    dag = reference.decode_genome(s["genome"], nodes)
    batch0 = x_full[jnp.asarray(prog["batch_idx"][0])]

    def run(q):
        qf = reference.quantizer(q)
        p, trace, _ = reference.train(
            tree, s["genome"], nodes, x_full, y_full, prog["batch_idx"], jnp.asarray(s["key"]),
            learning_rate=sched["learning_rate"], epoch_steps=sched["epoch_steps"],
            momentum=sched["momentum"], dropout_rate=sched["dropout_rate"], quantize=qf)
        with reference.jax.default_matmul_precision("highest"):
            logits = np.asarray(reference.forward(tree, batch0, dag, quantize=qf))
        return {"params": p, "logits": logits, "dparam_norms": _leaf_norms(_sub(p, tree)),
                "trace_norms": _leaf_norms(trace)}

    ref = run(None)
    # The validation pass: the network the reference trained, a head fitted
    # to its hidden rows of the training part, scored on the blended rows.
    fit = prog["fit_rows"][: int(check["fit_rows"])]
    x_check = jnp.asarray(prog["x_check"])
    hidden = lambda rows, q=None: reference.hidden_rows(
        ref["params"], s["genome"], nodes, x_check, rows, quantize=reference.quantizer(q))
    head = reference.fit_head(hidden(fit), ctx.y[fit], ctx.config["data"]["n_classes"])
    n_plain, y_plain = len(prog["plain"]), np.asarray(ctx.y)[prog["plain"]]
    first = reference.head_classes(hidden(prog["val_rows"]), head)
    ref.update(first=first, acc=float((first[:n_plain] == y_plain).mean()),
               fitted={**{k: {n: np.asarray(a) for n, a in v.items()} for k, v in ref["params"].items()},
                       "Dense_1": head})
    if quantize:
        ctl = run(quantize)
        low = reference.head_classes(hidden(prog["val_rows"], quantize), head)
        stated = reference.head_classes(hidden(prog["val_rows"], ctx.config["model"]["compute_dtype"]), head)
        ctl.update(acc=float((low[:n_plain] == y_plain).mean()),
                   flip=float((low != first)[n_plain:].mean()),
                   flip_stated=float((stated != first)[n_plain:].mean()))
        ref["control"] = ctl
    return ref


def _system_numbers(ctx, prog, refs) -> Dict[str, Any]:
    """The system's half after the window: its compiled validation pass on
    the reference's networks at every width, and its module's logits."""
    import jax
    import jax.numpy as jnp

    from gentun_tpu.models import cnn

    model_cfg, data, cfg = ctx.config["model"], ctx.config["data"], prog["cfg"]
    x_full, x_check = jnp.asarray(ctx.x, jnp.float32), jnp.asarray(prog["x_check"])
    val_idx = jnp.asarray(prog["val_idx"])
    evals = []
    for w in prog["widths"]:
        members = refs[: len(w["slots"])]
        params = w["stacked"]([r["fitted"] for r in members])
        score = lambda labels, rows: np.asarray(prog["eval_pop"](
            params, w["masks"], x_check, jnp.asarray(labels, jnp.int32), val_idx,
            jnp.asarray(prog[rows + "_weight"])), np.float64)[w["slots"]]
        acc = score(ctx.y, "plain")
        agree = []
        for i, r in enumerate(members):
            labels = np.array(ctx.y, np.int32)
            labels[prog["val_rows"]] = r["first"]  # the class the reference puts first
            agree.append(score(labels, "blended")[i])
        evals.append({"width": w["width"], "acc": [float(a) for a in acc],
                      "flip": [float(1.0 - a) for a in agree]})
    # Logits: the system's module under the population vmap, configured dtype.
    w = prog["widths"][0]
    module = cnn.MaskedGeneticCnn(
        nodes=tuple(model_cfg["nodes"]), filters=tuple(model_cfg["kernels_per_layer"]),
        dense_units=model_cfg["dense_units"], n_classes=data["n_classes"],
        dropout_rate=cfg["dropout_rate"], compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        stage_exit_conv=bool(cfg["stage_exit_conv"]))
    forward = jax.jit(jax.vmap(
        lambda p, m, xb: module.apply({"params": p}, xb, m, train=False), in_axes=(0, 0, None)))
    start = [reference.seeded_params(s["weights_seed"], *prog["shape_args"]) for s in prog["sampled"]]
    logits = np.asarray(forward(w["stacked"](start), w["masks"],
                                x_full[jnp.asarray(prog["batch_idx"][0])]))[w["slots"]]
    return {"evals": evals, "logits": logits}


def compare(ctx, prog, refs, system=None) -> Dict[str, float]:
    """The worst of each number over the sampled genomes and the widths.
    ``system`` None: the control's numbers in the program's place."""
    worst = {"logit_gap": 0.0, "dparam_gap": 0.0, "trace_gap": 0.0,
             "acc_gap": 0.0, "eval_flip": 0.0, "eval_ref_acc": min(r["acc"] for r in refs)}
    up = lambda k, v: worst.__setitem__(k, max(worst.get(k, 0.0), float(v)))
    for i, r in enumerate(refs):
        if system is None:
            c = r["control"]
            trained = [(c["dparam_norms"], c["trace_norms"])]
            logits, accs, flips = c["logits"], [c["acc"]], [c["flip"]]
            up("eval_flip_stated", c["flip_stated"])  # the reference in the stated precision: a yardstick
        else:
            trained = [(w["dparam_norms"][i], w["trace_norms"][i])
                       for w in prog["widths"] if i < len(w["slots"])]
            logits = system["logits"][i]
            accs = [e["acc"][i] for e in system["evals"] if i < len(e["acc"])]
            flips = [e["flip"][i] for e in system["evals"] if i < len(e["flip"])]
        up("logit_gap", np.abs(logits - r["logits"]).max() / np.abs(r["logits"]).max())
        for dnorms, tnorms in trained:
            up("dparam_gap", worst_leaf_gap(dnorms, r["dparam_norms"]))
            up("trace_gap", worst_leaf_gap(tnorms, r["trace_norms"]))
        for acc in accs:
            up("acc_gap", abs(acc - r["acc"]))
        for flip in flips:
            up("eval_flip", flip)
    return worst


def after_window(ctx, prog, control: Optional[str] = None
                 ) -> Tuple[List[Dict[str, Any]], Optional[Dict[str, float]]]:
    """Each number compared, beside its limit; and, with ``control``, what the
    reference computed in that lower precision reads in the program's place."""
    check = ctx.config["check"]
    refs = [_reference_numbers(ctx, prog, s, control) for s in prog["sampled"]]
    system = _system_numbers(ctx, prog, refs)
    for e in system["evals"]:
        print(f"info eval_pop at width {e['width']}: acc {e['acc']} flip {e['flip']}; "
              f"reference acc {[r['acc'] for r in refs[:len(e['acc'])]]}")
    sound = compare(ctx, prog, refs, system)
    out = []
    for k, v in sound.items():
        if k == "eval_ref_acc":
            floor = check["eval_ref_acc_floor"]
            out.append({"name": k, "value": v, "limit": f">{floor}", "ok": bool(v > floor)})
        else:
            out.append({"name": k, "value": v, "limit": check["limits"][k],
                        "ok": bool(v <= check["limits"][k])})
    return out, (compare(ctx, prog, refs) if control else None)
