"""Genetic-CNN fitness model: a masked supergraph trained under one XLA program.

Reference parity: ``GeneticCnnModel`` in ``gentun/models/keras_models.py``
[PUB] (SURVEY.md §2.0 row 9, §3.4).  Behaviors preserved:

- decode binary genes → per-stage DAG of Conv(3×3)+ReLU nodes, sum-merge
  fan-in, default input/output nodes, isolated nodes dropped;
- max-pool 2×2 between stages; dense head with dropout;
- SGD with a staged learning-rate schedule given as parallel tuples, e.g.
  ``epochs=(20, 4, 1)``, ``learning_rate=(1e-2, 1e-3, 1e-4)``;
- k-fold cross-validation; fitness = mean validation accuracy.

TPU-first architecture (NOT how the reference does it — SURVEY.md §7
"hard parts" #1):

- **One compiled program for the whole search space.**  The reference builds
  a fresh Keras graph per genome; a naive port would pay an XLA compile per
  individual, which on an 8k-architecture search space can dwarf train time.
  Here the network is a *supergraph* over all ``K_s`` nodes per stage, and a
  genome enters as mask **arrays** (``ops/dag.py``) — data, not structure.
  Every genome shares one jitted train function.
- **Whole populations train as one batched program.**  ``vmap`` over the
  (params, masks) population axis turns N independent CNN trainings into a
  single XLA computation whose matmuls are N-times wider — exactly what the
  MXU wants.  This is `cross_validate_population`, the hook
  ``Population.evaluate`` uses.
- **bfloat16 compute, float32 params/logits** by default on TPU: conv math
  rides the MXU at double rate while SGD accumulates in float32.
- Static shapes everywhere: fold sizes are equalised by trimming, train
  batches are a precomputed ``(steps, batch)`` index array consumed by
  ``lax.scan``, eval uses padded index batches with 0/1 weights.
- **The k-fold axis stays on device** (SURVEY.md §7 "hard parts" #3): the
  dataset lives on device ONCE and folds are expressed as index arrays —
  no per-fold host round-trips, no per-fold transfers.
- **Segmented execution**: long schedules run as a host loop of
  bounded-length jitted calls (``segment_steps`` ≈ tens of seconds each)
  over device-resident carries — params, optimizer state, and the dropout
  rng never leave the device, and the optax schedule continues across
  segments via the opt-state step count.  Segmenting bounds every device
  execution while keeping the population axis vmapped, so the matmul
  widths are unchanged and the per-call dispatch overhead (~ms against
  ~tens of seconds) is noise.
"""

from __future__ import annotations

import functools
import logging
import time
import weakref
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn
import optax

from ..ops.dag import stack_genome_masks
from ..parallel.mesh import (
    SIZE_SMALL,
    auto_mesh,
    classify_genome_cost,
    cnn_genome_cost,
    get_mesh_override,
    mesh_axis_sizes,
    mesh_factor,
    pad_population,
    pop_bucket,
)
from ..parallel.multihost import fetch, place, place_tree
from ..telemetry import lineage as _lineage
from ..telemetry import spans as _tele
from ..telemetry.registry import get_registry as _get_registry
from ..utils.xla_cache import oom_cap_key, read_oom_cap, resolved_cache_dir, write_oom_cap
from .evaluation import (
    base_keys,
    evaluation_prelude,
    fold_content_keys,
    genome_hashes,
    phase,
)
from .generic import GentunModel

__all__ = ["MaskedGeneticCnn", "GeneticCnnModel"]

logger = logging.getLogger("gentun_tpu")


class MaskedGeneticCnn(nn.Module):
    """The stage-DAG supergraph as a Flax module.

    ``masks`` is a list (one entry per stage) of dicts with keys
    ``adj (k, k)``, ``active (k,)``, ``entry (k,)``, ``exit (k,)``,
    ``has_active ()`` — see :func:`gentun_tpu.ops.dag.decode_stage`.  All
    mask values participate only multiplicatively, so the module traces to
    the same XLA program for every genome and is freely ``vmap``-able over a
    leading population axis on the masks.

    Stage recipe (reference recipe is [UNCERTAIN] per SURVEY.md §3.4; this
    is the documented rebuild choice): entry Conv3×3(F_s)+ReLU produces the
    default input node; each supergraph node is Conv3×3(F_s)+ReLU over the
    masked sum of its predecessors (+ stage input for entry nodes); the
    default output node sums exit-node outputs (identity pass-through when
    the stage decodes empty); 2×2 max-pool closes the stage.  Head:
    Dense(dense_units)+ReLU → Dropout → Dense(n_classes), logits in float32.

    ``stage_exit_conv=True`` switches to the Xie & Yuille variant where the
    default OUTPUT node applies its own Conv3×3(F_s)+ReLU after the sum
    (ADVICE r1: most Genetic-CNN implementations do; the default stays off
    to preserve round-1 behavior).  The conv is applied unconditionally to
    the merged stage output — shape-static, so one compiled program and the
    population vmap are preserved.
    """

    nodes: Tuple[int, ...]
    filters: Tuple[int, ...]
    dense_units: int = 500
    n_classes: int = 10
    dropout_rate: float = 0.5
    compute_dtype: Any = jnp.bfloat16
    stage_exit_conv: bool = False

    @nn.compact
    def __call__(self, x, masks, train: bool = False):
        dtype = self.compute_dtype
        x = x.astype(dtype)
        for s, k in enumerate(self.nodes):
            m = masks[s]
            f = self.filters[s]
            conv = functools.partial(
                nn.Conv, features=f, kernel_size=(3, 3), padding="SAME", dtype=dtype
            )
            a0 = nn.relu(conv(name=f"stage{s}_entry")(x))
            adj = m["adj"].astype(dtype)
            entry = m["entry"].astype(dtype)
            active = m["active"].astype(dtype)
            exit_ = m["exit"].astype(dtype)
            has_active = m["has_active"].astype(dtype)
            outs: List[jax.Array] = []
            for j in range(k):
                with jax.named_scope(f"stage{s}/mask_sum"):
                    inp = entry[j] * a0
                    for i in range(j):
                        inp = inp + adj[i, j] * outs[i]
                h = nn.relu(conv(name=f"stage{s}_node{j}")(inp))
                # Zero inactive nodes so they cannot leak into any sum.
                with jax.named_scope(f"stage{s}/gate"):
                    outs.append(active[j] * h)
            if k:
                with jax.named_scope(f"stage{s}/merge"):
                    out = outs[0] * exit_[0]
                    for i in range(1, k):
                        out = out + exit_[i] * outs[i]
                    x = has_active * out + (1.0 - has_active) * a0
            else:
                x = a0
            if self.stage_exit_conv:
                x = nn.relu(conv(name=f"stage{s}_exit")(x))
            with jax.named_scope(f"stage{s}/pool"):
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
        with jax.named_scope("head"):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(self.dense_units, dtype=dtype)(x))
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
            # Final projection + logits in float32: cheap, and keeps the
            # softmax/cross-entropy numerics out of bfloat16.
            x = nn.Dense(self.n_classes, dtype=jnp.float32)(x.astype(jnp.float32))
        return x


# ---------------------------------------------------------------------------
# Compiled population-training factory
# ---------------------------------------------------------------------------
#
# Everything static (architecture config, schedule, step counts) is baked
# into the factory key; everything genome- or data-dependent flows in as
# arrays.  The lru_cache means a whole GA search — hundreds of evaluations —
# compiles exactly once per (config, fold-shape) pair.


@functools.lru_cache(maxsize=32)
def _fold_segment_fns(
    nodes: Tuple[int, ...],
    filters: Tuple[int, ...],
    dense_units: int,
    n_classes: int,
    dropout_rate: float,
    compute_dtype: str,
    epochs: Tuple[int, ...],
    learning_rate: Tuple[float, ...],
    momentum: float,
    nesterov: bool,
    batch_size: int,
    n_train: int,
    n_val_padded: int,
    stage_exit_conv: bool,
    eval_batch_size: int,
    microbatch: int = 1,
):
    """Per-fold building blocks of the segmented executor, lru-cached by
    static config: the arguments are exactly :func:`_static_key`'s tuple.

    Returns ``(init_pop, train_pop, eval_pop)``, each jitted with the
    population axis vmapped over the model and its staged-LR SGD:

    - ``init_pop(params) -> opt_state``
    - ``train_pop(params, opt_state, masks, x, y, batch_idx_seg, rng)``
      runs one bounded segment of train steps and returns the advanced
      carries; the optax schedule continues across segments through the
      opt-state step count, so chopping the schedule is semantically
      invisible.
    - ``eval_pop(params, masks, x, y, val_idx, val_weight) -> acc``

    ``eval_batch_size`` may exceed ``batch_size``: the validation pass is
    forward-only (no optimizer state, no activations kept for backward), so
    larger batches amortise per-batch overhead and widen the MXU work with
    no memory downside.

    ``microbatch > 1`` (big-genome regime, DISTRIBUTED.md) splits each
    optimizer step's batch into that many slices and accumulates their
    gradients with an inner scan before the ONE optimizer update, cutting
    peak backward-pass activations by the same factor while keeping the
    step count, the LR schedule position, and the gradient expectation
    unchanged (mean of slice means = full-batch mean; dropout draws differ
    because the mask shape follows the slice).  ``microbatch=1`` traces
    the exact pre-existing step — the ``if`` below is Python-level, so the
    compiled program (and its persistent-cache key) is byte-identical to
    before the knob existed.
    """
    model = MaskedGeneticCnn(
        nodes=nodes,
        filters=filters,
        dense_units=dense_units,
        n_classes=n_classes,
        dropout_rate=dropout_rate,
        compute_dtype=jnp.dtype(compute_dtype),
        stage_exit_conv=stage_exit_conv,
    )
    steps_per_epoch = n_train // batch_size
    if steps_per_epoch == 0:
        raise ValueError(f"batch_size {batch_size} exceeds fold train size {n_train}")
    # Staged LR: boundaries at epoch-group ends, in units of optimizer steps
    # (gentun's parallel (epochs, learning_rate) tuples — SURVEY.md §3.4).
    boundaries_and_scales = {}
    step_mark = 0
    for n_ep, lr_prev, lr_next in zip(epochs[:-1], learning_rate[:-1], learning_rate[1:]):
        step_mark += n_ep * steps_per_epoch
        # A zero-epoch group lands two transitions on one step; their scales
        # must compound rather than overwrite.
        boundaries_and_scales[step_mark] = (
            boundaries_and_scales.get(step_mark, 1.0) * lr_next / lr_prev
        )
    schedule = optax.piecewise_constant_schedule(learning_rate[0], boundaries_and_scales)
    tx = optax.sgd(schedule, momentum=momentum, nesterov=nesterov)

    def loss_fn(params, masks, batch_x, batch_y, dropout_rng):
        logits = model.apply(
            {"params": params}, batch_x, masks, train=True, rngs={"dropout": dropout_rng}
        )
        with jax.named_scope("loss"):
            return optax.softmax_cross_entropy_with_integer_labels(logits, batch_y).mean()

    def train_segment(params, opt_state, masks, x_full, y_full, batch_idx_seg, rng):
        """Scan any number of train steps; carries advance, schedule
        position rides the opt-state step count."""

        def step(carry, idx_b):
            params, opt_state, rng = carry
            rng, dropout_rng = jax.random.split(rng)
            if microbatch > 1:
                idx_m = idx_b.reshape(microbatch, batch_size // microbatch)

                def micro(acc, im):
                    with jax.named_scope("gather"):
                        bx = jnp.take(x_full, im, axis=0)
                        by = jnp.take(y_full, im, axis=0)
                    _, g = jax.value_and_grad(loss_fn)(params, masks, bx, by, dropout_rng)
                    return jax.tree.map(jnp.add, acc, g), None

                grads, _ = jax.lax.scan(
                    micro, jax.tree.map(jnp.zeros_like, params), idx_m
                )
                grads = jax.tree.map(lambda g: g / microbatch, grads)
            else:
                with jax.named_scope("gather"):
                    batch_x = jnp.take(x_full, idx_b, axis=0)
                    batch_y = jnp.take(y_full, idx_b, axis=0)
                _, grads = jax.value_and_grad(loss_fn)(params, masks, batch_x, batch_y, dropout_rng)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return (params, opt_state, rng), None

        (params, opt_state, rng), _ = jax.lax.scan(
            step, (params, opt_state, rng), batch_idx_seg
        )
        return params, opt_state, rng

    def eval_fold(params, masks, x_full, y_full, val_idx, val_weight):
        def eval_batch(correct, start):
            idx_b = jax.lax.dynamic_slice_in_dim(val_idx, start, eval_batch_size, axis=0)
            wb = jax.lax.dynamic_slice_in_dim(val_weight, start, eval_batch_size, axis=0)
            with jax.named_scope("gather"):
                xb = jnp.take(x_full, idx_b, axis=0)
                yb = jnp.take(y_full, idx_b, axis=0)
            logits = model.apply({"params": params}, xb, masks, train=False)
            # Materialize the logits before the argmax.  Fused into the
            # vmapped forward, XLA:TPU (jax 0.9.0 / libtpu 0.0.34) returns
            # index 0 for every example of some population slots — 8 of 20
            # at the CIFAR-10 width, whatever genome sits there — while the
            # logits themselves are right (PERF.md, PR 21).  The barrier is
            # an identity; chip_smoke.py's slot-order check guards it.
            with jax.named_scope("score"):
                logits = jax.lax.optimization_barrier(logits)
                hits = (jnp.argmax(logits, axis=-1) == yb).astype(jnp.float32)
                return correct + jnp.sum(hits * wb), None

        starts = jnp.arange(0, n_val_padded, eval_batch_size)
        correct, _ = jax.lax.scan(eval_batch, jnp.float32(0.0), starts)
        return correct / jnp.maximum(val_weight.sum(), 1.0)

    init_pop = jax.jit(jax.vmap(tx.init))
    # Donate the carries: each call consumes the previous segment's params /
    # opt state / rng, halving peak HBM versus keeping both generations.
    train_pop = jax.jit(
        jax.vmap(train_segment, in_axes=(0, 0, 0, None, None, None, 0)),
        donate_argnums=(0, 1, 6),
    )
    eval_pop = jax.jit(jax.vmap(eval_fold, in_axes=(0, 0, None, None, None, None)))
    return init_pop, train_pop, eval_pop


def _eval_batch_size(batch_size: int, n_val: int) -> Tuple[int, int]:
    """(eval_batch_size, n_val_padded) for a validation block of n_val rows.

    Forward-only eval takes up to 4× the train batch — fewer scan
    iterations, wider MXU work, no backward-memory cost.  The batch is
    sized by dividing the block into the fewest ≤4×batch segments rather
    than fixing it at 4×batch, so padding never exceeds what the train
    batch size alone would cause (plus segment-count rounding), instead of
    up to ~60% for unlucky block sizes.
    """
    if n_val <= 0:
        return batch_size, 0
    rounded = int(np.ceil(n_val / batch_size)) * batch_size
    n_seg = max(1, int(np.ceil(rounded / (4 * batch_size))))
    eval_bs = int(np.ceil(rounded / n_seg))
    return eval_bs, eval_bs * n_seg


def _static_key(cfg: Dict[str, Any], batch_size: int, n_train: int, n_val_padded: int,
                eval_batch_size: int) -> Tuple:
    """The ONE definition of the compiled-program static key:
    :func:`_fold_segment_fns` keys on exactly this tuple, so a program
    compiled for one config can never silently serve another.
    """
    return (
        cfg["nodes"],
        cfg["kernels_per_layer"],
        cfg["dense_units"],
        cfg["n_classes"],
        cfg["dropout_rate"],
        cfg["compute_dtype"],
        cfg["epochs"],
        cfg["learning_rate"],
        cfg["momentum"],
        cfg["nesterov"],
        batch_size,
        n_train,
        n_val_padded,
        bool(cfg["stage_exit_conv"]),
        eval_batch_size,
        int(cfg.get("microbatch", 1) or 1),
    )


def _segment_bounds(total_steps: int, segment_steps) -> List[Tuple[int, int]]:
    """Chop ``total_steps`` into bounded segments (at most 2 distinct sizes,
    so at most 2 compiled shapes)."""
    if not segment_steps or segment_steps >= total_steps:
        return [(0, total_steps)]
    seg = int(segment_steps)
    return [(s, min(s + seg, total_steps)) for s in range(0, total_steps, seg)]


def _carry_devices(carries) -> int:
    """Fewest devices any leaf of the train carries has a shard on.

    Rides the ``train`` span so a run on several chips can show that the
    pop-sharded params / optimizer state / rng really are spread over the
    mesh: a leaf left behind on one device reads 1 here whatever the mesh
    says.
    """
    return min(
        len({s.device for s in leaf.addressable_shards})
        for leaf in jax.tree_util.tree_leaves(carries)
    )


def _mesh_shardings(mesh):
    """``(pop, batch, replicated)`` shardings of the segmented executor on
    ``mesh``; three ``None`` without one."""
    if mesh is None:
        return None, None, None
    from jax.sharding import NamedSharding, PartitionSpec as P

    return (NamedSharding(mesh, P("pop")), NamedSharding(mesh, P(None, "data")),
            NamedSharding(mesh, P()))


def _put(x, sharding):
    """Host value → device: plain upload off-mesh; on a mesh through
    parallel.multihost.place, which is plain device_put single-process and
    the multi-controller-legal make_array path when this worker spans
    several hosts."""
    return jnp.asarray(x) if sharding is None else place(x, sharding)


def _run_segmented(
    cfg: Dict[str, Any],
    masks,
    carries,
    x_np,
    y_np,
    val_idx,
    val_weight,
    batch_idx,
    mesh,
    batch_size: int,
    n_train: int,
    n_val_padded: int,
    eval_batch_size: int,
    warm_keys=None,
) -> np.ndarray:
    """Host loop over folds × bounded segments; returns (kfold, P) accs.

    ``masks`` and ``carries`` are :func:`_fold_carries`' outputs: each fold's
    starting ``(params, rng)`` is already a tree of its own on the device (and
    on the mesh), so the per-fold prologue is one compiled ``init_pop`` and no
    indexing of device arrays.  Every device call is short (``segment_steps``
    train steps), every carry (params, opt state, rng) stays device-resident,
    and the dataset uploads once — so the only host↔device traffic per
    segment is one tiny index array.  Whatever the first ``train_pop`` does
    not need (cost calibration, validation indices, later folds' index
    arrays) runs after it is dispatched, while the device is busy.
    """
    init_pop, train_pop, eval_pop = _fold_segment_fns(
        *_static_key(cfg, batch_size, n_train, n_val_padded, eval_batch_size)
    )
    _, batch_s, repl = _mesh_shardings(mesh)
    x_full, y_full = _put(x_np, repl), _put(y_np, repl)

    kfold, total_steps = batch_idx.shape[0], batch_idx.shape[1]
    bounds = _segment_bounds(total_steps, cfg["segment_steps"])
    tele = _tele.enabled()
    pop_dim = int(next(iter(masks[0].values())).shape[0]) if masks else 0
    mesh_sizes = list(mesh_axis_sizes(mesh))
    accs = []
    for f in range(kfold):
        with phase("fold_slice", {"fold": f}):
            p, rng_f = carries[f]
            opt = init_pop(p)
        for s, e in bounds:
            seg = _put(batch_idx[f, s:e], batch_s)
            with phase(
                "train", {"steps": e - s, "pop": pop_dim, "fold": f, "mesh": mesh_sizes},
                program=(id(train_pop), e - s, pop_dim, kfold),
            ) as sp:
                p, opt, rng_f = sp.fence(train_pop(p, opt, masks, x_full, y_full, seg, rng_f))
                if tele:
                    sp.set(carry_devices=_carry_devices((p, opt, rng_f)))
            if f == 0 and s == 0:
                _record_cost_calibration(cfg, p, pop_dim)
        vi, vw = _put(val_idx[f], repl), _put(val_weight[f], repl)
        # Keep the result ON device: materialising here would block the host
        # until fold f finishes and leave the device idle while the host
        # prepares fold f+1.  jax dispatch is async, so appending the device
        # array keeps the execution queue full across folds; params/opt
        # buffers still die at loop end (acc is tiny).
        with phase("eval", {"pop": pop_dim, "fold": f},
                    program=(id(eval_pop), pop_dim, kfold)) as sp:
            accs.append(sp.fence(eval_pop(p, masks, x_full, y_full, vi, vw)))
        if f == 0 and warm_keys is not None:
            # Deposit BEFORE the carry dies: fold 0's trained params become
            # the warm-start seed a later higher-rung evaluation of the
            # same genome inherits (``_warm_bank_deposit``).
            _warm_bank_deposit(p, warm_keys)
        del p, opt
    # fetch = np.asarray single-process; an all-gather of the pop-sharded
    # accuracies when the mesh spans processes (every host gets the full
    # vector, keeping the SPMD ranks in lockstep).
    with phase("fetch"):
        return np.stack([fetch(a).astype(np.float32) for a in accs])


def _init_slot(model: MaskedGeneticCnn, input_shape: Tuple[int, ...], key, masks):
    """One slot's fresh parameters.  ``model.init`` runs a full forward pass;
    unjitted it dispatches op by op (3+ seconds per generation measured on
    the chip in July 2026), so it is only ever traced, by :func:`_carry_fn`."""
    dummy = jnp.zeros((1, *input_shape), dtype=jnp.float32)
    return model.init({"params": key}, dummy, masks, train=False)["params"]


#: Keeps train_and_score's streams disjoint from CV fold 0's (same formula,
#: kfold=1 → fold index 0) so a holdout training under the search's own seed
#: can never bit-replicate the CV training it is supposed to independently
#: check.
_HOLDOUT_DOMAIN = 0x5C04E


@functools.lru_cache(maxsize=32)
def _carry_fn(model: MaskedGeneticCnn, input_shape: Tuple[int, ...], kfold: int, pop_sharding=None):
    """One compiled program from genome hashes to every fold's starting carry.

    ``build(init_base, train_base, hashes, masks)`` returns a list of
    ``kfold`` pairs ``(params, rng)`` with a leading population axis: the
    key derivation of :func:`~.evaluation.fold_content_keys` for both
    streams, the per-(fold, slot) ``model.init`` and the split by fold all
    happen inside it, so a call pays one dispatch where eager code paid about
    a hundred at the flagship's 34 leaves (two key chains, then one slice per
    parameter leaf per fold).  Each fold trains from an independent init
    (seed folded per fold), matching the reference's fresh model per CV
    fold.  Bit-identical on CPU to the stacked eager derivation
    (``tests/test_carry_builder.py`` keeps it as its oracle).  Cached per
    (module — flax modules are frozen dataclasses, so they hash by config —
    input shape, kfold, the mesh's ``pop`` sharding or None); jax
    re-specialises it per population width.
    """
    init_pop_slots = jax.vmap(functools.partial(_init_slot, model, input_shape))

    def build(init_base, train_base, hashes, masks):
        return [
            (init_pop_slots(fold_content_keys(init_base, f, hashes), masks),
             fold_content_keys(train_base, f, hashes))
            for f in range(kfold)
        ]

    return jax.jit(build, out_shardings=pop_sharding)


def _fold_carries(cfg: Dict[str, Any], model: MaskedGeneticCnn, stacked, hashes, kfold: int, mesh, domain: int = 0):
    """``(masks, carries)`` of the segmented executor: the stacked masks on the
    device (``pop``-sharded on a mesh) and :func:`_carry_fn`'s per-fold
    ``(params, rng)`` — born with the ``pop`` sharding, so nothing is
    re-placed.  ``domain`` separates callers (train_and_score vs CV) that
    would otherwise replicate each other's fold-0 streams under one seed; the
    base keys are four tiny cached programs."""
    pop_s, _, repl = _mesh_shardings(mesh)
    init_base, train_base = base_keys(cfg["seed"], domain)
    masks = stacked
    if mesh is not None:
        masks = place_tree(stacked, pop_s)
        hashes, init_base, train_base = place(hashes, pop_s), place(init_base, repl), place(train_base, repl)
    build = _carry_fn(model, tuple(cfg["input_shape"]), kfold, pop_s)
    return masks, build(init_base, train_base, hashes, masks)


#: Parent→child weight bank for multi-fidelity warm starts (``warm_start``
#: knob; DISTRIBUTED.md "Multi-fidelity evolution").  Keyed by the 64-bit
#: genome content hash (both ``genome_hashes`` words), so a promoted
#: genome finds exactly ITS lower-rung parameters — never a sibling's —
#: regardless of batch composition or slot order.  Values are host-numpy
#: single-slot param trees (the trained fold-0 carry), insertion-ordered
#: for LRU eviction.  Process-local BY DESIGN: a promotion landing on a
#: different worker cold-starts, which is always correct (warm start is a
#: pure speedup, never a correctness dependency), and nothing crosses the
#: wire — genes in, fitness out stays intact.
_WARM_BANK: Dict[Tuple[int, int], Any] = {}
_WARM_BANK_CAP = 64


def _warm_bank_deposit(params_f0, hashes) -> None:
    """Bank each slot's trained fold-0 params, keyed by genome content hash.

    ``params_f0`` leaves are (P, ...) device arrays; fetching them here is
    the only host transfer the warm-start path adds, and it happens once
    per evaluation AFTER fold 0's work is already queued — the device keeps
    training fold 1 while the host copies.
    """
    leaves, treedef = jax.tree.flatten(params_f0)
    host = [np.array(fetch(leaf)) for leaf in leaves]
    for i in range(len(hashes)):
        key = (int(hashes[i][0]), int(hashes[i][1]))
        _WARM_BANK.pop(key, None)
        _WARM_BANK[key] = jax.tree.unflatten(treedef, [h[i] for h in host])
    while len(_WARM_BANK) > _WARM_BANK_CAP:
        del _WARM_BANK[next(iter(_WARM_BANK))]


def _warm_start_overlay(carries, hashes):
    """Overlay banked lower-rung params onto fresh inits, where shapes match.

    ``carries`` is :func:`_fold_carries`' list of per-fold ``(params, rng)``;
    a banked slot is copied into its slot of EVERY fold's params (each fold
    still sees an independent dropout/batch stream, only the starting point
    is shared).  A leaf whose shape or dtype disagrees with the bank (the
    genome was banked under a different static config) keeps its fresh init
    — partial inheritance is the contract, matching per-layer
    shape-compatible transfer.  Nothing is fetched unless the bank holds one
    of ``hashes``.  Returns (carries, slots_warmed).
    """
    treedef = jax.tree.structure(carries[0][0])
    host = None  # per fold, the params' leaves as host arrays
    warmed = 0
    for i in range(len(hashes)):
        key = (int(hashes[i][0]), int(hashes[i][1]))
        banked = _WARM_BANK.get(key)
        if banked is None:
            continue
        _WARM_BANK[key] = _WARM_BANK.pop(key)  # LRU touch
        b_leaves, b_def = jax.tree.flatten(banked)
        if b_def != treedef:
            continue
        if host is None:
            host = [[np.array(fetch(leaf)) for leaf in jax.tree.leaves(p)] for p, _ in carries]
        hit = False
        for j, bl in enumerate(b_leaves):
            if bl.shape == host[0][j].shape[1:] and bl.dtype == host[0][j].dtype:
                for fold_leaves in host:
                    fold_leaves[j][i] = bl
                hit = True
        if hit:
            warmed += 1
            # Lineage: identity here is the weight bank's CONTENT key (the
            # genome-mask hash pair), not telemetry.lineage.genome_key — the
            # bank never sees genes, only stacked masks.
            _lineage.record(
                "warm_started", "bank:%x:%x" % key, slot=i)
    if host is None:
        return carries, 0
    return [
        (jax.tree.unflatten(treedef, [jnp.asarray(h) for h in fold_leaves]), rng)
        for fold_leaves, (_, rng) in zip(host, carries)
    ], warmed


#: (id(x_key), id(y_key), fingerprints, seed, n_use, input_shape) →
#: (weakref(x_key), weakref(y_key), x_dev, y_dev).  Kept tiny (a handful of
#: datasets); entries are validated by object identity through the
#: weakrefs, so a recycled id can never alias, and by a strided content
#: fingerprint, so in-place mutation (e.g. per-generation augmentation)
#: is detected instead of silently training on stale device data.
_DATASET_CACHE: Dict[Tuple, Tuple[Any, Any, Any, Any]] = {}


def _content_fingerprint(a) -> Tuple[Any, ...]:
    """Cheap content hash: shape/dtype + a ≤1024-element strided sample.

    O(1 KiB) regardless of dataset size, so it runs on every cache probe.
    A mutation that misses every sampled element still goes undetected —
    the documented contract remains "don't mutate in place" — but the
    common cases (normalisation, augmentation, relabeling) touch enough of
    the array to flip the sample with near-certainty.
    """
    arr = np.asarray(a)
    flat = arr.ravel()
    step = max(1, flat.size // 1024)
    sample = np.ascontiguousarray(flat[::step][:1024])
    return (arr.shape, str(arr.dtype), hash(sample.tobytes()))


def _device_dataset(key_x, key_y, xp: np.ndarray, yp: np.ndarray, perm: np.ndarray, cfg: Dict[str, Any], mesh=None):
    """Device-resident permuted dataset, cached across evaluate() calls.

    Uploading the dataset dominated a warm proxy evaluation on the chip
    (~4.3s of 7.4s measured in July 2026 for CIFAR-10-sized data) and a GA pays it
    every generation even though the dataset never changes within a search.

    The cache is keyed by the identity of the CALLER's arrays (``key_x`` /
    ``key_y`` — the objects a Population holds stable across generations)
    plus a strided content fingerprint, never by the ``_prepare_data``
    outputs, which are fresh objects on every call whenever a reshape/dtype
    conversion happens.  The fingerprint turns the "arrays must not be
    mutated in place" contract (documented on ``GeneticCnnModel``) from an
    assumption into a near-certain cache miss when violated.  Eviction is
    LRU one-at-a-time, so the hot dataset survives a fifth dataset showing
    up; dead-referent entries are dropped eagerly.
    """
    with phase("dataset") as sp:
        xd, yd, found = _dataset_lookup(key_x, key_y, xp, yp, perm, cfg, mesh)
        sp.set(source="found" if found else "uploaded")
        return xd, yd


def _dataset_lookup(key_x, key_y, xp, yp, perm, cfg, mesh):
    """:func:`_device_dataset` proper: (x, y, whether the cache had them)."""
    # Evict dead entries eagerly so device copies never outlive their host
    # arrays just because the cache hasn't hit its size bound.
    for k in [k for k, (xr, yr, *_dv) in _DATASET_CACHE.items() if xr() is None or yr() is None]:
        del _DATASET_CACHE[k]
    key = (
        id(key_x),
        id(key_y),
        _content_fingerprint(key_x),
        _content_fingerprint(key_y),
        int(cfg["seed"]),
        int(len(perm)),
        cfg["input_shape"],
        mesh,  # Mesh hashes by devices+axes; None single-chip
    )
    hit = _DATASET_CACHE.get(key)
    if hit is not None:
        xref, yref, xd, yd = hit
        if xref() is key_x and yref() is key_y:
            _DATASET_CACHE[key] = _DATASET_CACHE.pop(key)  # LRU: refresh recency
            return xd, yd, True
    # Same arrays, different fingerprint ⇒ the caller mutated in place; the
    # predecessor entries can never hit again, so drop them now instead of
    # pinning stale device copies of the same dataset until LRU catches up.
    # (Same ids + same fingerprints with a different seed/n/shape are
    # legitimate sibling entries — e.g. the holdout path — and stay.)
    for k in [
        k for k in _DATASET_CACHE
        if k[0] == key[0] and k[1] == key[1] and (k[2], k[3]) != (key[2], key[3])
    ]:
        del _DATASET_CACHE[k]
    if mesh is not None:
        # Cache the GLOBALLY-placed arrays: under a multi-process mesh a
        # post-hoc re-placement would round-trip the whole dataset through
        # the host every generation — the exact cost this cache kills.
        from jax.sharding import NamedSharding, PartitionSpec

        repl = NamedSharding(mesh, PartitionSpec())
        xd, yd = place(xp[perm], repl), place(yp[perm], repl)
    else:
        xd, yd = jnp.asarray(xp[perm]), jnp.asarray(yp[perm])
    try:
        xref, yref = weakref.ref(key_x), weakref.ref(key_y)
    except TypeError:
        return xd, yd, False  # un-weakref-able input (e.g. a list): don't cache
    while len(_DATASET_CACHE) >= 4:  # datasets are big; keep device HBM bounded
        _DATASET_CACHE.pop(next(iter(_DATASET_CACHE)))  # LRU eviction
    _DATASET_CACHE[key] = (xref, yref, xd, yd)
    return xd, yd, False


#: Per-config cap on how many genomes one compiled program may carry,
#: learned from device OOMs (see _chunked_by_cap).  Keyed by the shape-
#: relevant config fingerprint so a memory-hungry deep config's cap never
#: throttles a small config evaluated later in the same process.  A cap of
#: 2 or more is also kept in the persistent cache directory
#: (``utils/xla_cache.py``, ``.oom_caps.json``), where the next process of
#: the same configuration, device and compiler finds it before its first
#: attempt; this dictionary is what a process consults after that.
_POP_PROGRAM_CAP: Dict[Any, int] = {}

#: cap_keys whose cap=1 exact-size routing has already been warned about
#: (once per config per process — the consequence is ongoing, the log
#: line shouldn't be).
_EXACT_ROUTE_WARNED: set = set()


def _oom_cap_key(cfg: Dict[str, Any]):
    """Every config field that changes a program's per-genome memory —
    configs differing in ANY of these must not share a learned cap."""
    return (
        tuple(cfg["nodes"]),
        tuple(cfg["kernels_per_layer"]),
        int(cfg["batch_size"]),
        int(cfg["dense_units"]),
        str(cfg["compute_dtype"]),
        tuple(cfg["input_shape"]),
        int(cfg["n_classes"]),
        cfg["segment_steps"],
        int(cfg["kfold"]) if cfg.get("kfold") else None,
        int(cfg.get("microbatch", 1) or 1),
    )


def _is_oom_error(e: BaseException) -> bool:
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "out of memory" in s.lower()


def _record_oom_split(t0: float, genomes: int, cap: int) -> None:
    """The healer just learned a cap: the ``oom_split`` event, and the failed
    attempt since ``t0`` as an ``oom_attempt`` span — what the OOM cost.
    Retroactive because only its end tells an attempt that OOMs from one
    that does not."""
    attrs = {"genomes": genomes, "cap": cap}
    _tele.record_event("oom_split", attrs)
    _tele.record_span("oom_attempt", t0, time.monotonic() - t0, attrs=dict(attrs))


def _cap_entry(cfg: Optional[Dict[str, Any]], cap_key) -> Optional[Tuple[str, str]]:
    """Where this configuration's cap outlives the process: the cache
    directory its evaluations enable and the key of its entry there.  None
    where it does not: no configuration given, the cache off, or a backend
    with no memory limit to key by (``xla_cache.oom_cap_key``).  The mesh's
    part of the key is the factoring a population as wide as the devices
    gets, so it does not follow the size of the batch at hand."""
    if cfg is None:
        return None
    cache_dir = resolved_cache_dir(cfg["cache_dir"])
    if cache_dir is None:
        return None
    mesh = cfg["mesh"]
    if mesh == "auto":
        axes = get_mesh_override() or mesh_factor(jax.device_count())
    else:
        axes = mesh_axis_sizes(mesh)
    key = oom_cap_key(cap_key, axes)
    return None if key is None else (cache_dir, key)


def _restored_cap(cfg: Optional[Dict[str, Any]], cap_key, genomes: int) -> Optional[int]:
    """The cap an earlier process learned for this configuration on this
    device, read before the first attempt: made this process's own, with an
    ``oom_cap_restored`` event and ``oom_cap_restored_total``.  ``oom_split``
    and ``oom_attempt`` keep meaning that this process paid an attempt.  A
    cap of 1 is never inherited (see ``_chunked_by_cap``)."""
    entry = _cap_entry(cfg, cap_key)
    cap = read_oom_cap(*entry) if entry else None
    if cap is None or cap < 2:
        return None
    _POP_PROGRAM_CAP[cap_key] = cap
    _tele.record_event("oom_cap_restored", {"genomes": genomes, "cap": cap})
    _get_registry().counter("oom_cap_restored_total").inc()
    logger.info("chunking to <=%d genomes per program: the cap an earlier process "
                "learned for this config on this device (%s)", cap, entry[0])
    return cap


def _chunked_by_cap(run, genomes, cap_key, run_exact=None, cfg=None):
    """Run the batched evaluator, splitting the population on device OOM.

    BASELINE config #5 (S=(5,5,5), 256 channels, pop=50) is sized for a
    pod slice; vmapping all 50 genomes through one program exhausts a
    single chip's HBM.  Instead of dying, split to a power-of-two chunk
    (so the chunks reuse the standard bucket shapes — no compile churn)
    and REMEMBER the cap for this config fingerprint: later generations
    pre-chunk instead of re-discovering the OOM.  On a big mesh the pop
    axis shards and no OOM ever happens, so the cap stays unset and
    behavior is unchanged.

    With ``cfg`` (the normalised config) the cap outlives the process: it
    is written beside the compiled programs in the persistent cache
    directory (``_cap_entry``), and a process whose own dictionary holds
    nothing for the config looks there BEFORE its first attempt and
    pre-chunks exactly as it would after healing — same widths, same
    programs, same order of chunks.  So the failed attempt is paid once a
    cache directory, not once a process.  A restored cap is where the
    attempts start, not a promise: a chunk that still runs out of memory
    heals as below and the smaller cap replaces the entry; the file never
    raises a cap this process has learned.  A restarted search whose first
    batch is smaller than the population therefore chunks it at the width
    the earlier process ran at, which is the width its fitness cache was
    filled under.

    ``run_exact`` is the unpadded (exact-size) runner: since the compile
    bucket floors at 2, a singleton chunk padded by ``run`` still executes
    a 2-wide program, so a learned cap of 1 is only honorable — and a
    last-genome OOM only survivable — by dropping the padding.  Once
    cap=1 is learned, EVERY evaluation for that config routes through the
    1-wide unpadded program, so batch-composition purity is gone for the
    rest of the search (values measured before the boundary came from
    multi-slot programs) — survival over purity, warned once per config.
    That is why a cap of 1 is never written and never restored: a process
    re-learns it rather than inherit it from one bad moment of another.
    """
    cap = _POP_PROGRAM_CAP.get(cap_key)
    if cap is None:
        cap = _restored_cap(cfg, cap_key, len(genomes))
    if cap is not None and len(genomes) > cap:
        return np.concatenate(
            [_chunked_by_cap(run, genomes[i : i + cap], cap_key, run_exact, cfg)
             for i in range(0, len(genomes), cap)]
        )
    if cap == 1 and len(genomes) == 1 and run_exact is not None:
        if cap_key not in _EXACT_ROUTE_WARNED:
            _EXACT_ROUTE_WARNED.add(cap_key)
            logger.warning(
                "config with learned memory cap=1: all its evaluations now "
                "run 1-wide unpadded — fitnesses measured before this "
                "boundary came from numerically distinct multi-slot "
                "programs (batch-composition purity does not hold across "
                "the cap=1 boundary)",
            )
        return run_exact(genomes)
    fallback = None
    t0 = time.monotonic()
    try:
        return run(genomes)
    except Exception as e:
        if not _is_oom_error(e):
            raise
        if len(genomes) <= 1:
            if run_exact is None:
                raise
            _POP_PROGRAM_CAP[cap_key] = 1
            _record_oom_split(t0, 1, 1)
            logger.warning(
                "singleton population batch exhausted device memory in its "
                "padded (2-wide) program; retrying exact-size (1-wide, "
                "unpadded — batch-composition purity does not hold for "
                "this genome)",
            )
            fallback = run_exact
        else:
            half = max(1, len(genomes) // 2)
            b = 1
            while b * 2 <= half:
                b *= 2
            _POP_PROGRAM_CAP[cap_key] = b
            _record_oom_split(t0, len(genomes), b)
            entry = _cap_entry(cfg, cap_key) if b > 1 else None
            if entry:
                write_oom_cap(*entry, b)
            logger.warning(
                "population batch of %d genomes exhausted device memory; "
                "chunking to <=%d genomes per program (remembered for this "
                "config in this process%s)", len(genomes), b,
                f", and in {entry[0]} for the next" if entry else "",
            )
    # Retry OUTSIDE the except block, deliberately: the failed attempt's
    # exception traceback pins the frames (and therefore the device
    # buffers) of the too-large execution — recursing inside the handler
    # chains those exceptions and accumulates dead HBM until even a
    # 1-genome program cannot allocate (measured on the deep config).
    # Leaving the handler drops the traceback; collect to free the
    # buffers before the smaller chunks run.
    import gc

    gc.collect()
    if fallback is not None:
        return fallback(genomes)
    return _chunked_by_cap(run, genomes, cap_key, run_exact, cfg)


# Compile-shape bucketing moved to parallel/mesh.pop_bucket so the
# dispatch plane derives worker capacity from the SAME policy the
# evaluator compiles to (host_worker_capacity); the historical name stays
# importable here.  populations._compile_bucket is the jax-free mirror.
_pop_bucket = pop_bucket


def _genome_size_class(cfg: Dict[str, Any]) -> Tuple[str, int]:
    """(size_class, microbatch) for this config against its device budget.

    The evaluator-side classification (big-genome regime, DISTRIBUTED.md):
    same cost model the dispatch plane's ``job_size_class`` consults, but
    LOUD — an unevaluable genome raises here with full context instead of
    degrading, because this is the process that would otherwise OOM.  The
    class is a property of the CONFIG (the supergraph runs every node conv
    regardless of mask bits), so one evaluation batch has exactly one
    class.  No budget configured → the wide-pop path, bit-identically.
    """
    budget = cfg.get("device_budget")
    if not budget:
        return SIZE_SMALL, 1
    cost = cnn_genome_cost(
        cfg["nodes"],
        cfg["kernels_per_layer"],
        cfg["input_shape"],
        cfg["dense_units"],
        cfg["n_classes"],
        cfg["compute_dtype"],
        bool(cfg["stage_exit_conv"]),
    )
    return classify_genome_cost(
        cost, int(cfg["batch_size"]), jax.device_count(), int(budget)
    )


def _account_sharded_batch(cfg: Dict[str, Any], mesh, batch_size: int, steps: int) -> None:
    """Fit the microbatch factor to the ACTUAL step batch and account waste.

    Called by both evaluators once the clamped ``batch_size`` is known
    (small folds can shrink it below ``cfg['batch_size']``), BEFORE the
    static key is read:

    - ``cfg['microbatch']`` is clamped to the batch and bumped to the next
      divisor, so the accumulation reshape is always exact;
    - ``microbatch_steps_total`` counts the micro-gradient passes this
      evaluation will run (train steps × factor) whenever accumulation is
      active;
    - ``eval_data_pad_waste_total`` counts batch slots the data axis pads
      per step (GSPMD pads uneven shards internally; those lanes are
      wasted work exactly like pop-padding slots), summed over the
      evaluation's steps — the data-axis sibling of
      ``eval_pad_waste_total``, surfaced next to it in ``/statusz``.
    """
    micro = int(cfg.get("microbatch", 1) or 1)
    if micro > 1:
        micro = min(micro, batch_size)
        while batch_size % micro:
            micro += 1
        cfg["microbatch"] = micro
        _get_registry().counter("microbatch_steps_total").inc(steps * micro)
    _, data_ax = mesh_axis_sizes(mesh)
    shard_rem = batch_size % data_ax
    if shard_rem:
        _get_registry().counter("eval_data_pad_waste_total").inc(
            (data_ax - shard_rem) * steps
        )


def _record_cost_calibration(cfg: Dict[str, Any], params, n_slots: int) -> None:
    """Calibrate the dispatch cost model against what jax actually built.

    The scheduling plane sizes genomes with ``cnn_genome_cost`` — a static
    prediction.  Every evaluation call is a free chance to measure how far
    that prediction sits from reality — free because the segmented executor
    calls this after its first train dispatch, while the device is busy — so
    record both sides as ``genome_cost_calibration{size_class,source}``
    gauges:

    - ``predicted_param_bytes`` / ``predicted_act_bytes_batch``: the cost
      model's claim (params×3 f32 convention; activations in compute dtype
      for one full batch);
    - ``measured_param_bytes``: per-genome-slot bytes of the parameter tree
      × 3 (params + momentum + grads, the same convention the prediction
      uses), leaves divided by the ``n_slots`` (``P``) of one fold's tree;
    - ``device_bytes_in_use``: the backend allocator's own number when it
      has one (TPU/GPU ``memory_stats``; absent on CPU) — the largest over
      the local devices, since the fullest device is the one that OOMs.

    Fleet-side, the aggregator surfaces these per size class so a drifting
    cost model is visible before it mis-schedules a big genome.  Fail-soft:
    calibration must never be able to kill an evaluation.
    """
    try:
        size_class, _ = _genome_size_class(cfg)
        cost = cnn_genome_cost(
            cfg["nodes"],
            cfg["kernels_per_layer"],
            cfg["input_shape"],
            cfg["dense_units"],
            cfg["n_classes"],
            cfg["compute_dtype"],
            bool(cfg["stage_exit_conv"]),
        )
        reg = _get_registry()

        def _gauge(source: str, value: float) -> None:
            reg.gauge(
                "genome_cost_calibration", size_class=size_class, source=source
            ).set(float(value))

        _gauge("predicted_param_bytes", cost.param_bytes)
        _gauge(
            "predicted_act_bytes_batch",
            cost.act_bytes_per_example * int(cfg["batch_size"]),
        )
        leaf_bytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
        )
        _gauge("measured_param_bytes", 3 * leaf_bytes / max(1, n_slots))
        in_use = [
            stats["bytes_in_use"]
            for stats in (d.memory_stats() for d in jax.local_devices())
            if stats and "bytes_in_use" in stats
        ]
        if in_use:
            _gauge("device_bytes_in_use", max(in_use))
    except Exception as exc:  # pragma: no cover - diagnostics only
        logger.debug("cost calibration skipped: %s", exc)


#: Mesh shape of the previous evaluation in this process — feeds the
#: ``mesh_reshapes_total`` counter (docs/OBSERVABILITY.md): every flip is
#: a sharding layout change, and interleaving size classes carelessly
#: shows up here as churn the dispatch plane's class-grouping should have
#: prevented.
_LAST_MESH_SHAPE: Optional[Tuple[int, int]] = None


def _prepare_population_setup(cfg: Dict[str, Any], genomes: Sequence[Mapping[str, Any]]):
    """Shared entry-point setup: the evaluation prelude (compile cache,
    publish hooks), resolve the mesh, pad the population to the compile-shape bucket and
    the pop-axis size, stack genome masks, and build the module.  One
    definition for both ``cross_validate_population`` and
    ``train_and_score``.
    """
    evaluation_prelude(cfg["cache_dir"])

    # Multi-chip: shard the population axis over the mesh (and the train
    # batch over its data axis).  Pad so the pop axis divides evenly;
    # callers slice results back to the original length (n_real).
    # The mesh derives from the BUCKETED size: deriving it from the raw
    # size would give different small batches different mesh factorings
    # (and therefore fresh compiles) even though they pad to one shape.
    target = _pop_bucket(len(genomes)) if cfg["pop_padding"] else len(genomes)
    size_class, _ = _genome_size_class(cfg)
    mesh = cfg["mesh"]
    if mesh == "auto":
        mesh = auto_mesh(pop_size=target, size_class=size_class)
    multiple = mesh.shape["pop"] if mesh else 1
    if cfg["pop_padding"]:
        # honor the mesh multiple on top of the bucket
        if target % multiple:
            target += multiple - target % multiple
        # len(genomes) <= target < 2*target, so padding to a multiple of
        # `target` is padding to exactly `target`.
        genomes, n_real = pad_population(genomes, target)
    else:
        genomes, n_real = pad_population(genomes, multiple)
    # Mesh observability: the axis sizes this evaluation actually shards
    # over, and the padding slots this batch wastes (slots trained whose
    # results are sliced away — a mesh-aligned dispatch schedule keeps
    # this at 0; see DISTRIBUTED.md "Host-level mesh workers").  Plain
    # registry writes — a couple of dict ops, cheap enough to stay
    # unconditional so `/metrics` is truthful even with spans off.
    _reg = _get_registry()
    _pop_ax, _data_ax = mesh_axis_sizes(mesh)
    _reg.gauge("mesh_pop_axis").set(_pop_ax)
    _reg.gauge("mesh_data_axis").set(_data_ax)
    global _LAST_MESH_SHAPE
    if _LAST_MESH_SHAPE is not None and (_pop_ax, _data_ax) != _LAST_MESH_SHAPE:
        _reg.counter("mesh_reshapes_total").inc()
    _LAST_MESH_SHAPE = (_pop_ax, _data_ax)
    if len(genomes) > n_real:
        _reg.counter("eval_pad_waste_total").inc(len(genomes) - n_real)
    # One batched upload of the whole mask tree (float32 throughout).
    stacked = jax.device_put(stack_genome_masks(genomes, cfg["nodes"]))
    model = MaskedGeneticCnn(
        nodes=cfg["nodes"],
        filters=cfg["kernels_per_layer"],
        dense_units=cfg["dense_units"],
        n_classes=cfg["n_classes"],
        dropout_rate=cfg["dropout_rate"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        stage_exit_conv=bool(cfg["stage_exit_conv"]),
    )
    return mesh, genomes, n_real, len(genomes), stacked, model, genome_hashes(genomes)


class GeneticCnnModel(GentunModel):
    """Train the decoded CNN under k-fold CV; fitness = mean val accuracy.

    Drop-in counterpart of the reference's ``GeneticCnnModel``
    (``gentun/models/keras_models.py`` [PUB]).  Config knobs mirror the
    reference's constructor (SURVEY.md §3.4), all optional:

    - ``nodes=(3, 5)``: stage node counts (must match the genome).
    - ``kernels_per_layer=(20, 50)``: per-stage conv channels.
    - ``input_shape``: HWC; inferred from ``x_train`` when omitted (flat
      inputs are reshaped to it).
    - ``kfold=5``; ``epochs=(20, 4, 1)``; ``learning_rate=(1e-2, 1e-3, 1e-4)``;
      ``batch_size=128``; ``dense_units=500``; ``dropout_rate=0.5``;
      ``n_classes`` (inferred); ``momentum=0.9``; ``nesterov=False``;
      ``compute_dtype='bfloat16'``; ``seed=0``.

    Execution knobs (rebuild-specific): ``segment_steps=96`` bounds each
    device call of the segmented executor (None = one call per fold);
    ``stage_exit_conv`` adds the Xie & Yuille output-node
    conv — measured at the full schedule on two workloads, the bare-sum
    default matched or beat it on CV and holdout accuracy, so False stays
    the default (docs/STAGE_EXIT_CONV.md has the table); ``mesh``/
    ``cache_dir`` control sharding and the persistent compilation cache;
    ``device_budget`` (bytes per device, default off) turns on the
    big-genome regime — configs whose cost model exceeds it leave the
    wide-pop vmap path for a narrow-pop data-sharded mesh, with
    ``microbatch`` gradient accumulation when even a full-data-axis batch
    shard oversubscribes (DISTRIBUTED.md "Big-genome regime";
    ``microbatch`` may also be set directly).

    Data contract: ``x_train``/``y_train`` are treated as immutable — the
    permuted dataset is cached on device across ``evaluate()`` calls, keyed
    by array identity plus a strided content fingerprint.  Mutating them in
    place between calls is detected (near-certainly) and triggers a
    re-upload; prefer replacing the arrays to mutating them.
    """

    def __init__(
        self,
        x_train,
        y_train,
        genes: Mapping[str, Any],
        nodes: Sequence[int] = (3, 5),
        input_shape: Optional[Sequence[int]] = None,
        kernels_per_layer: Sequence[int] = (20, 50),
        kfold: int = 5,
        epochs: Sequence[int] = (20, 4, 1),
        learning_rate: Sequence[float] = (1e-2, 1e-3, 1e-4),
        batch_size: int = 128,
        dense_units: int = 500,
        dropout_rate: float = 0.5,
        n_classes: Optional[int] = None,
        momentum: float = 0.9,
        nesterov: bool = False,
        compute_dtype: str = "bfloat16",
        seed: int = 0,
        mesh="auto",
        cache_dir: Optional[str] = None,
        stage_exit_conv: bool = False,
        segment_steps: Optional[int] = 96,
        pop_padding: bool = True,
        fitness_reps: int = 1,
        device_budget: Optional[int] = None,
        microbatch: int = 1,
    ):
        super().__init__(x_train, y_train, genes)
        self.config = dict(
            nodes=tuple(int(k) for k in nodes),
            input_shape=tuple(input_shape) if input_shape is not None else None,
            kernels_per_layer=tuple(int(f) for f in kernels_per_layer),
            kfold=int(kfold),
            epochs=tuple(int(e) for e in epochs),
            learning_rate=tuple(float(r) for r in learning_rate),
            batch_size=int(batch_size),
            dense_units=int(dense_units),
            dropout_rate=float(dropout_rate),
            n_classes=n_classes,
            momentum=float(momentum),
            nesterov=bool(nesterov),
            compute_dtype=str(compute_dtype),
            seed=int(seed),
            mesh=mesh,
            cache_dir=cache_dir,
            stage_exit_conv=bool(stage_exit_conv),
            segment_steps=segment_steps,
            pop_padding=bool(pop_padding),
            fitness_reps=int(fitness_reps),
            device_budget=device_budget,
            microbatch=int(microbatch),
        )

    def cross_validate(self) -> float:
        return float(
            self.cross_validate_population(self.x_train, self.y_train, [self.genes], **self.config)[0]
        )

    # -- the population-batched path (used by Population.evaluate) ---------

    @classmethod
    def cross_validate_population(
        cls,
        x_train,
        y_train,
        genomes: Sequence[Mapping[str, Any]],
        **config,
    ) -> np.ndarray:
        """k-fold CV fitness for P genomes in one vmapped program per fold.

        Returns an array of P mean validation accuracies.  All genomes train
        simultaneously: the population axis is vmapped, so XLA sees one
        computation with P-wide batched convolutions.  A population too
        large for the device's memory (deep configs on few chips) is
        chunked automatically, with the learned cap reused across
        generations and, through the persistent cache directory, across
        processes (``_chunked_by_cap``).
        """
        reps_raw = config.get("fitness_reps", 1)
        reps = 1 if reps_raw is None else int(reps_raw)
        # reps < 1 falls through to _normalize_config, which raises.
        if reps > 1:
            # Noise-reduced fitness (VERDICT r4 weak #1): average each
            # genome's CV accuracy over `reps` fully independent trainings,
            # one call per rep with a derived seed.  Each rep differs in
            # init, dropout, shuffle order AND fold assignment — the same
            # independence the holdout estimator uses — and the derived
            # seed only changes input arrays (index tables, PRNG keys), so
            # all reps share one compiled program.  Deliberately NOT
            # implemented by tiling reps into the population axis: the
            # learned OOM cap (`_chunked_by_cap`) can split a tiled batch
            # into position-aligned chunks whose copies would train
            # bit-identically, silently averaging away nothing.
            inner = {**config, "fitness_reps": 1}
            base_seed = int(config.get("seed", 0) or 0)
            per_rep = [
                cls.cross_validate_population(
                    x_train, y_train, genomes, **{**inner, "seed": base_seed + 7919 * r}
                )
                for r in range(reps)
            ]
            return np.mean(per_rep, axis=0, dtype=np.float64).astype(np.float32)
        cfg0 = _normalize_config(x_train, y_train, config)
        size_class, micro = _genome_size_class(cfg0)
        if size_class != SIZE_SMALL:
            # Big-genome regime: the cost model says the wide-pop vmap
            # cannot fit, so run ONE genome per program on the narrow-pop
            # (1, n_devices) mesh with the batch sharded across the full
            # data axis (pop_padding off: the 1-wide exact program IS the
            # intended shape here, not an OOM fallback).  No
            # _chunked_by_cap — its pop-splitting cannot help a program
            # that is already 1 genome wide.
            sub = {**config, "pop_padding": False, "microbatch": micro}
            outs = [
                cls._cross_validate_population_one(x_train, y_train, [g], **sub)
                for g in genomes
            ]
            return (
                np.concatenate(outs) if outs else np.zeros((0,), dtype=np.float32)
            )
        return _chunked_by_cap(
            lambda gs: cls._cross_validate_population_one(x_train, y_train, gs, **config),
            list(genomes),
            _oom_cap_key(cfg0),
            run_exact=lambda gs: cls._cross_validate_population_one(
                x_train, y_train, gs, **{**config, "pop_padding": False}
            ),
            cfg=cfg0,
        )

    @classmethod
    def _cross_validate_population_one(
        cls,
        x_train,
        y_train,
        genomes: Sequence[Mapping[str, Any]],
        **config,
    ) -> np.ndarray:
        """One chunk of a population through k-fold CV.  With telemetry on,
        one ``cv_call`` span whose children name every host phase of the
        call, in order (docs/OBSERVABILITY.md)."""
        with phase("cv_call", {"n_real": len(genomes)}) as call:
            with phase("prepare"):
                cfg = _normalize_config(x_train, y_train, config)
                x, y = _prepare_data(x_train, y_train, cfg)
                if len(genomes) == 0:
                    return np.zeros((0,), dtype=np.float32)
                mesh, genomes, n_real, pop, stacked, model, hashes = _prepare_population_setup(cfg, genomes)
            call.set(pop=pop)

            kfold = cfg["kfold"]
            n = x.shape[0]
            if kfold < 2:
                raise ValueError("kfold must be >= 2")
            fold_size = n // kfold
            if fold_size == 0:
                raise ValueError(f"kfold={kfold} exceeds dataset size {n}")
            with phase("index_build"):
                n_use = fold_size * kfold  # equal folds → one compiled shape
                rng = np.random.default_rng(cfg["seed"])
                perm = rng.permutation(n)[:n_use]
                # The device-resident dataset is x[perm]; folds are consecutive
                # position blocks within it, so every index array below addresses
                # x_full/y_full directly.
                folds = np.arange(n_use, dtype=np.int32).reshape(kfold, fold_size)

                batch_size = min(cfg["batch_size"], n_use - fold_size)
                n_tr = n_use - fold_size
                steps_per_epoch = max(n_tr // batch_size, 1)
                total_steps = sum(cfg["epochs"]) * steps_per_epoch
                eval_bs, n_val_padded = _eval_batch_size(batch_size, fold_size)
                pad = n_val_padded - fold_size
                _account_sharded_batch(cfg, mesh, batch_size, total_steps * kfold)

                # Per-fold index arrays (host-side numpy, tiny): the fold IS its
                # indices.  batch_idx holds *global* dataset indices, so the compiled
                # program gathers straight from the one device-resident copy of x.
                batch_idx = np.zeros((kfold, total_steps, batch_size), dtype=np.int32)
                val_idx = np.zeros((kfold, n_val_padded), dtype=np.int32)
                val_weight = np.zeros((kfold, n_val_padded), dtype=np.float32)
                for f in range(kfold):
                    tr_idx = np.concatenate([folds[g] for g in range(kfold) if g != f])
                    order = np.concatenate(
                        [rng.permutation(n_tr) for _ in range(sum(cfg["epochs"]))]
                    )[: total_steps * batch_size]
                    batch_idx[f] = tr_idx[order].reshape(total_steps, batch_size)
                    val_idx[f] = np.concatenate([folds[f], np.full(pad, folds[f][0])])
                    val_weight[f] = np.concatenate(
                        [np.ones(fold_size, np.float32), np.zeros(pad, np.float32)]
                    )

            with phase("init_params"):
                masks, carries = _fold_carries(cfg, model, stacked, hashes, kfold, mesh)
                # Parent→child weight inheritance (multi-fidelity ladder): overlay
                # each slot's own lower-rung trained params where shapes match, and
                # bank fold-0 results for the NEXT rung.  Single-process only: on a
                # multi-process mesh the gather would stall every rank for a
                # process-local cache, so it falls back to cold starts, which is
                # always correct (pure speedup).
                warm = cfg["warm_start"] and mesh is None
                if warm:
                    carries, warmed = _warm_start_overlay(carries, hashes[:n_real])
                    if warmed:
                        logger.debug("warm start: %d/%d slots inherited banked params",
                                     warmed, n_real)

            x_dev, y_dev = _device_dataset(x_train, y_train, x, y, perm, cfg, mesh)

            accs = _run_segmented(
                cfg, masks, carries, x_dev, y_dev,
                val_idx, val_weight, batch_idx, mesh, batch_size, n_tr,
                n_val_padded, eval_bs,
                warm_keys=hashes[:n_real] if warm else None,
            )
            return accs.mean(axis=0)[:n_real]

    # -- final holdout evaluation (not part of the reference's API) --------

    @classmethod
    def train_and_score(
        cls,
        x_train,
        y_train,
        x_test,
        y_test,
        genomes: Sequence[Mapping[str, Any]],
        **config,
    ) -> np.ndarray:
        reps_raw = config.get("fitness_reps", 1)
        reps = 1 if reps_raw is None else int(reps_raw)
        # reps < 1 falls through to _normalize_config, which raises.
        if reps > 1:
            # Same per-rep derived-seed protocol as
            # cross_validate_population: mean holdout accuracy over `reps`
            # fully independent trainings.
            inner = {**config, "fitness_reps": 1}
            base_seed = int(config.get("seed", 0) or 0)
            per_rep = [
                cls.train_and_score(
                    x_train, y_train, x_test, y_test, genomes,
                    **{**inner, "seed": base_seed + 7919 * r},
                )
                for r in range(reps)
            ]
            return np.mean(per_rep, axis=0, dtype=np.float64).astype(np.float32)
        cfg0 = _normalize_config(x_train, y_train, config)
        size_class, micro = _genome_size_class(cfg0)
        if size_class != SIZE_SMALL:
            # Same big-genome routing as cross_validate_population.
            sub = {**config, "pop_padding": False, "microbatch": micro}
            outs = [
                cls._train_and_score_one(x_train, y_train, x_test, y_test, [g], **sub)
                for g in genomes
            ]
            return (
                np.concatenate(outs) if outs else np.zeros((0,), dtype=np.float32)
            )
        return _chunked_by_cap(
            lambda gs: cls._train_and_score_one(x_train, y_train, x_test, y_test, gs, **config),
            list(genomes),
            _oom_cap_key(cfg0),
            run_exact=lambda gs: cls._train_and_score_one(
                x_train, y_train, x_test, y_test, gs, **{**config, "pop_padding": False}
            ),
            cfg=cfg0,
        )

    @classmethod
    def _train_and_score_one(
        cls,
        x_train,
        y_train,
        x_test,
        y_test,
        genomes: Sequence[Mapping[str, Any]],
        **config,
    ) -> np.ndarray:
        """Train each genome on ALL of ``x_train`` and score on a held-out
        test set — the paper-style final number (the search itself uses
        :meth:`cross_validate_population`).

        Reuses the same compiled program family as CV: the holdout is
        expressed as a single "fold" whose train indices cover the train
        block and whose val indices cover the test block of one
        device-resident concatenated array.  Returns P test accuracies.
        """
        cfg = _normalize_config(x_train, y_train, config)
        x_tr, y_tr = _prepare_data(x_train, y_train, cfg)
        x_te, y_te = _prepare_data(x_test, y_test, cfg)
        if len(genomes) == 0:
            return np.zeros((0,), dtype=np.float32)
        mesh, genomes, n_real, pop, stacked, model, hashes = _prepare_population_setup(cfg, genomes)

        n_tr, n_te = x_tr.shape[0], x_te.shape[0]
        batch_size = min(cfg["batch_size"], n_tr)
        steps_per_epoch = max(n_tr // batch_size, 1)
        total_steps = sum(cfg["epochs"]) * steps_per_epoch
        eval_bs, n_val_padded = _eval_batch_size(batch_size, n_te)
        pad = n_val_padded - n_te
        _account_sharded_batch(cfg, mesh, batch_size, total_steps)

        rng = np.random.default_rng(cfg["seed"])
        order = np.concatenate(
            [rng.permutation(n_tr) for _ in range(sum(cfg["epochs"]))]
        )[: total_steps * batch_size]
        # Combined device-resident array: train block first, test block after.
        batch_idx = order.astype(np.int32).reshape(1, total_steps, batch_size)
        val_idx = (n_tr + np.concatenate([np.arange(n_te), np.zeros(pad)])).astype(np.int32)[None]
        val_weight = np.concatenate([np.ones(n_te, np.float32), np.zeros(pad, np.float32)])[None]

        # Domain-separate the holdout training from CV fold 0: without it,
        # train_and_score under the search's own seed would replicate the
        # CV fold-0 init/dropout streams bit-for-bit, correlating the
        # holdout estimate with the CV estimate it is supposed to check.
        masks, carries = _fold_carries(cfg, model, stacked, hashes, 1, mesh, domain=_HOLDOUT_DOMAIN)
        x_full = np.concatenate([x_tr, x_te], axis=0)
        y_full = np.concatenate([y_tr, y_te], axis=0)
        # The holdout is one "fold"; the segmented executor drives it with
        # the same bounded device calls as CV.
        accs = _run_segmented(
            cfg, masks, carries, x_full, y_full,
            val_idx, val_weight, batch_idx, mesh, batch_size, n_tr,
            n_val_padded, eval_bs,
        )
        return accs[0][:n_real]


def _normalize_config(x_train, y_train, config: Dict[str, Any]) -> Dict[str, Any]:
    """Fill inferred fields (input_shape, n_classes) and canonicalise types."""
    defaults = dict(
        nodes=(3, 5),
        input_shape=None,
        kernels_per_layer=(20, 50),
        kfold=5,
        epochs=(20, 4, 1),
        learning_rate=(1e-2, 1e-3, 1e-4),
        batch_size=128,
        dense_units=500,
        dropout_rate=0.5,
        n_classes=None,
        momentum=0.9,
        nesterov=False,
        compute_dtype="bfloat16",
        seed=0,
        mesh="auto",
        cache_dir=None,
        stage_exit_conv=False,
        segment_steps=96,
        pop_padding=True,
        fitness_reps=1,
        warm_start=False,
        device_budget=None,
        microbatch=1,
    )
    unknown = set(config) - set(defaults)
    if unknown:
        raise TypeError(f"unknown GeneticCnnModel parameters: {sorted(unknown)}")
    cfg = {**defaults, **config}
    cfg["nodes"] = tuple(int(k) for k in cfg["nodes"])
    cfg["kernels_per_layer"] = tuple(int(f) for f in cfg["kernels_per_layer"])
    if len(cfg["kernels_per_layer"]) != len(cfg["nodes"]):
        raise ValueError("kernels_per_layer must have one entry per stage")
    cfg["epochs"] = tuple(int(e) for e in cfg["epochs"])
    cfg["learning_rate"] = tuple(float(r) for r in cfg["learning_rate"])
    if len(cfg["epochs"]) != len(cfg["learning_rate"]):
        raise ValueError("epochs and learning_rate must be parallel tuples")
    if cfg["segment_steps"] is not None:
        cfg["segment_steps"] = int(cfg["segment_steps"])
        if cfg["segment_steps"] < 1:
            raise ValueError("segment_steps must be a positive int or None")
    cfg["fitness_reps"] = 1 if cfg["fitness_reps"] is None else int(cfg["fitness_reps"])
    if cfg["fitness_reps"] < 1:
        raise ValueError("fitness_reps must be a positive int")
    cfg["warm_start"] = bool(cfg["warm_start"])
    if cfg["device_budget"] is not None:
        cfg["device_budget"] = int(cfg["device_budget"])
        if cfg["device_budget"] < 1:
            raise ValueError("device_budget must be positive bytes or None")
    cfg["microbatch"] = 1 if cfg["microbatch"] is None else int(cfg["microbatch"])
    if cfg["microbatch"] < 1:
        raise ValueError("microbatch must be a positive int")
    x = np.asarray(x_train)
    if cfg["input_shape"] is None:
        if x.ndim == 4:
            cfg["input_shape"] = tuple(x.shape[1:])
        elif x.ndim == 3:
            cfg["input_shape"] = (*x.shape[1:], 1)
        else:
            raise ValueError(
                "input_shape is required for flat inputs (cannot infer HWC from "
                f"array of shape {x.shape})"
            )
    else:
        cfg["input_shape"] = tuple(int(d) for d in cfg["input_shape"])
    if cfg["n_classes"] is None:
        cfg["n_classes"] = int(np.max(np.asarray(y_train))) + 1
    cfg["n_classes"] = int(cfg["n_classes"])
    return cfg


def _prepare_data(x_train, y_train, cfg: Dict[str, Any]):
    """float32 NHWC images + int32 labels, reshaping flat inputs if needed."""
    x = np.asarray(x_train, dtype=np.float32)
    if x.ndim != 4:
        x = x.reshape((x.shape[0], *cfg["input_shape"]))
    y = np.asarray(y_train, dtype=np.int32)
    if x.shape[0] != y.shape[0]:
        raise ValueError(f"x/y length mismatch: {x.shape[0]} vs {y.shape[0]}")
    return x, y
