"""Device-mesh helpers: population × data parallelism for fitness training.

The reference's only parallelism is population-level task parallelism over
RabbitMQ workers, each training on a single GPU (SURVEY.md §2.2).  The
rebuild keeps that control-plane parallelism (``distributed/``) and adds the
one new axis the north star asks for: **multi-chip scaling inside a worker**
over a ``jax.sharding.Mesh``.

Two named axes:

- ``pop`` — shards the vmapped population axis of the batched trainer
  (``models/cnn.py``).  Individuals are independent, so this axis needs
  ZERO collectives: pure scale-out, the GA's dominant regime.
- ``data`` — shards the per-step training batch.  Params stay replicated
  along ``data``; XLA's sharding propagation inserts the gradient
  all-reduce over ICI automatically (GSPMD), which is the entire
  data-parallel implementation — no hand-written collectives, per the
  scaling-book recipe: pick a mesh, annotate shardings, let XLA insert
  collectives.

No function here changes the compiled computation: multi-chip execution is
driven purely by the shardings of the input arrays
(``models/cnn.py::_fold_carries``, ``_run_segmented``), which is what keeps
the single-chip and 32-chip paths one and the same jitted program.

**Big-genome regime** (DISTRIBUTED.md "Big-genome regime"): the pure-math
half of size-aware scheduling also lives here — a per-genome cost model
(:func:`cnn_genome_cost`: params + peak-activation bytes from the stage
DAG, integer arithmetic only) and its classification against a per-device
memory budget (:func:`classify_genome_cost`).  Small genomes keep the
wide-pop vmap path bit-identically; big genomes get a narrow-pop
``(1, n_devices)`` mesh with the per-step batch sharded across the FULL
data axis; genomes whose activations still exceed the budget at the
training batch size additionally accumulate gradients over microbatches.
Everything in this module up to :func:`auto_mesh` is importable and
callable WITHOUT jax — module-level jax imports are deliberately deferred
into the functions that build meshes or place arrays, so the dispatch
plane (broker counters, worker re-chunking, master fill targets) can
classify jobs without ever touching a backend.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Mapping, Sequence, Tuple

import numpy as np

__all__ = [
    "auto_mesh",
    "pad_population",
    "mesh_axis_sizes",
    "mesh_factor",
    "pop_bucket",
    "host_worker_capacity",
    "GenomeCost",
    "cnn_genome_cost",
    "classify_genome_cost",
    "job_size_class",
    "parse_mesh_spec",
    "set_mesh_override",
    "get_mesh_override",
    "SIZE_SMALL",
    "SIZE_BIG",
    "SIZE_MICRO",
    "SIZE_CLASSES",
]

#: Size classes the per-device memory budget sorts genomes into.  The class
#: decides the ``(pop, data)`` split: ``small`` keeps the wide-pop vmap
#: path (bit-identical to the pre-budget behavior), ``big`` runs one
#: genome per program with the batch sharded across the FULL data axis,
#: ``micro`` is ``big`` plus microbatch gradient accumulation.
SIZE_SMALL = "small"
SIZE_BIG = "big"
SIZE_MICRO = "micro"
SIZE_CLASSES = (SIZE_SMALL, SIZE_BIG, SIZE_MICRO)


def _largest_divisor_leq(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= cap (>=1)."""
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def mesh_factor(n_devices: int, pop_size: Optional[int] = None,
                size_class: str = "small") -> Tuple[int, int]:
    """The ``(pop, data)`` factoring :func:`auto_mesh` would build.

    Pure integer math — no device objects, no backend init — so the
    dispatch plane (worker capacity derivation, broker-side sizing) can
    reason about mesh shapes without touching jax.  Kept as THE factoring
    authority: ``auto_mesh`` calls this, which is what guarantees a
    worker's advertised mesh shape and its evaluation mesh agree.

    ``size_class`` (see :data:`SIZE_CLASSES`) flips the preference: the
    default ``small`` puts devices on the communication-free ``pop`` axis
    first; ``big``/``micro`` pin the narrow-pop ``(1, n)`` extreme so an
    over-budget genome's activations shard across the FULL data axis.
    """
    n = int(n_devices)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if size_class not in SIZE_CLASSES:
        raise ValueError(
            f"size_class must be one of {SIZE_CLASSES}, got {size_class!r}")
    if size_class != SIZE_SMALL:
        return 1, n
    cap = n if pop_size is None else max(1, int(pop_size))
    pop_axis = _largest_divisor_leq(n, cap)
    return pop_axis, n // pop_axis


def pop_bucket(n: int) -> int:
    """Round SMALL population batches up to a power of two (≤ 16).

    The population axis is a compile-time shape: a GA's later generations
    evaluate whatever the fitness cache didn't answer — small, varying
    batches (5, 2, 1, ...) — and each distinct size would otherwise pay a
    full XLA compile (minutes for CIFAR-scale configs).  Bucketing bounds a
    search to at most {2, 4, 8, 16} small shapes plus the full-population
    shape; waste is < 2× and only where the absolute cost is small.  Batches
    ≥ 16 stay exact — they are the dominant cost and occur at one stable
    size (the full population).

    The floor is 2, not 1: XLA compiles a singleton population axis to a
    different program (the vmap axis collapses) whose float rounding can
    flip a prediction vs the same genome trained in a wider batch —
    breaking the batch-composition purity that ``genome_hashes`` buys
    (measured: one-sample accuracy flip at pop=1 on CPU).  Bucket 2 keeps
    every padded batch on the same multi-slot program family.

    Canonical definition (``models/cnn._pop_bucket`` aliases it;
    ``populations._compile_bucket`` mirrors it jax-free — the lockstep
    test in ``tests/test_populations_speculative.py`` covers all three).
    """
    if n >= 16:
        return n
    b = 2
    while b < n:
        b *= 2
    return b


class GenomeCost(NamedTuple):
    """Per-genome memory footprint estimate, in bytes (pure host math).

    - ``param_bytes``: train-resident parameter state for ONE genome —
      params, SGD momentum, and one gradient tree, all float32.  Replicated
      along ``data``, so it never shrinks with the data axis.
    - ``act_bytes_per_example``: activations one training example keeps
      live for the backward pass, in the compute dtype.  Scales with the
      per-device batch shard, so the data axis divides it.
    """

    param_bytes: int
    act_bytes_per_example: int


def cnn_genome_cost(
    nodes: Sequence[int],
    filters: Sequence[int],
    input_shape: Sequence[int],
    dense_units: int,
    n_classes: int,
    compute_dtype: str = "bfloat16",
    stage_exit_conv: bool = False,
) -> GenomeCost:
    """Cost model for one ``MaskedGeneticCnn`` genome — integer math only.

    Same spirit as :func:`mesh_factor`: no jax, no device objects, cheap
    enough for the dispatch hot path (micro-gated in
    ``scripts/broker_throughput.py``).  Derived from the stage-DAG
    supergraph the evaluator actually compiles (``models/cnn.py``): every
    stage runs its entry conv plus ALL ``k`` node convs regardless of the
    mask bits (masks are data, not structure), so the footprint is a
    function of the config's widths, not of which edges a genome enables.

    Parameter state counts 3× float32 (params + momentum + grads);
    activations count one live copy per conv output per example at the
    stage's spatial resolution (halved by each 2×2 pool), in the compute
    dtype.  A model, not a measurement — monotone in stage widths, node
    counts, and batch size, which is all classification needs.
    """
    dtype_bytes = 2 if "16" in str(compute_dtype) else 4
    h, w = int(input_shape[0]), int(input_shape[1])
    c_in = int(input_shape[2]) if len(input_shape) > 2 else 1
    param_count = 0
    act_per_ex = h * w * c_in * dtype_bytes  # the input itself
    for k, f in zip(nodes, filters):
        k, f = int(k), int(f)
        param_count += 9 * c_in * f + f          # entry Conv3x3
        param_count += k * (9 * f * f + f)       # node Conv3x3s
        if stage_exit_conv:
            param_count += 9 * f * f + f
        # Live conv outputs per example: entry + k nodes + merged output
        # (+ the optional exit conv), all at (h, w, f).
        act_per_ex += (k + 2 + (1 if stage_exit_conv else 0)) * h * w * f * dtype_bytes
        h, w = max(1, h // 2), max(1, w // 2)    # 2x2 max-pool
        c_in = f
    flat = h * w * c_in
    param_count += flat * int(dense_units) + int(dense_units)
    param_count += int(dense_units) * int(n_classes) + int(n_classes)
    act_per_ex += (flat + int(dense_units)) * dtype_bytes + int(n_classes) * 4
    return GenomeCost(int(3 * 4 * param_count), int(act_per_ex))


def classify_genome_cost(
    cost: GenomeCost,
    batch_size: int,
    n_devices: int,
    budget_bytes: int,
) -> Tuple[str, int]:
    """Sort one genome's cost against a per-device budget → ``(class, microbatch)``.

    - ``small``: params + full-batch activations fit one device (<= budget,
      so an exactly-at-budget genome stays on the wide-pop path);
      microbatch 1.
    - ``big``: fits only with the per-step batch sharded across the FULL
      data axis of ``n_devices`` (params replicate; activations divide);
      microbatch 1.
    - ``micro``: even a full-axis batch shard oversubscribes — returns the
      smallest divisor of ``batch_size`` whose per-device micro-slice fits,
      for gradient accumulation.

    A genome that cannot hold its parameter state plus ONE example within
    the budget is unevaluable at any factoring: loud ``ValueError``, never
    a silent misclassification.
    """
    b = int(batch_size)
    n = max(1, int(n_devices))
    budget = int(budget_bytes)
    if b < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    if budget < 1:
        raise ValueError(f"device budget must be positive bytes, got {budget_bytes}")
    if cost.param_bytes + cost.act_bytes_per_example * b <= budget:
        return SIZE_SMALL, 1
    avail = budget - cost.param_bytes
    if avail < cost.act_bytes_per_example:
        raise ValueError(
            f"device budget {budget} bytes cannot hold this genome's parameter "
            f"state ({cost.param_bytes} bytes) plus one training example "
            f"({cost.act_bytes_per_example} bytes of activations) — the genome "
            f"is unevaluable at any (pop, data) factoring; raise the budget or "
            f"shrink the architecture")
    per_shard = -(-b // n)  # ceil: examples per device at the full data axis
    if cost.act_bytes_per_example * per_shard <= avail:
        return SIZE_BIG, 1
    for a in range(2, b + 1):
        if b % a == 0 and cost.act_bytes_per_example * (-(-(b // a) // n)) <= avail:
            return SIZE_MICRO, a
    return SIZE_MICRO, b  # a=b always fits per the one-example check above


#: Memo for :func:`job_size_class`, keyed on the cost-relevant wire-config
#: values.  A generation ships ONE ``additional_parameters`` config for its
#: whole population, so the dispatch hot path (one classify per dispatched
#: job) is a pure cache hit in steady state — what keeps the per-job cost
#: inside the ≤2 %-of-dispatch gate (``scripts/broker_throughput.py``).
#: Bounded: distinct configs are one-per-session-generation rare, but a
#: hostile stream of unique configs must not grow the broker unboundedly.
_JOB_CLASS_CACHE: Dict[tuple, str] = {}
_JOB_CLASS_CACHE_MAX = 4096


def _hashable(v: Any) -> Any:
    return tuple(v) if isinstance(v, list) else v


def job_size_class(params: Optional[Mapping[str, Any]], n_devices: int = 1) -> str:
    """Size class for a dispatch-plane job from its wire config dict.

    The jax-free entry point the broker's dispatch counter, the worker's
    ``_chunk_jobs``, and the master's fill target share.  Returns
    ``small`` whenever the feature is off (no ``device_budget`` in the
    shipped config) or the config lacks the fields the cost model needs
    (``input_shape``/``n_classes`` are usually inferred worker-side from
    the data) — degrading exactly like the broker's ``_parse_mesh``
    treats a malformed mesh advert, because dispatch must route jobs from
    any master version, while the evaluator's own classification stays
    loud (``models/cnn.py``).  Note ``small`` vs not is independent of
    ``n_devices``; the axis width only moves the big/micro boundary.
    """
    if not params:
        return SIZE_SMALL
    budget = params.get("device_budget")
    if not budget:
        return SIZE_SMALL
    try:
        input_shape = params.get("input_shape")
        n_classes = params.get("n_classes")
        if not input_shape or not n_classes:
            return SIZE_SMALL
        key = (
            _hashable(params.get("nodes")),
            _hashable(params.get("kernels_per_layer")),
            _hashable(input_shape),
            n_classes,
            params.get("dense_units"),
            params.get("batch_size"),
            params.get("compute_dtype"),
            params.get("stage_exit_conv"),
            budget,
            n_devices,
        )
        hit = _JOB_CLASS_CACHE.get(key)
        if hit is not None:
            return hit
        cost = cnn_genome_cost(
            tuple(params.get("nodes", (3, 5))),
            tuple(params.get("kernels_per_layer", (20, 50))),
            tuple(input_shape),
            int(params.get("dense_units", 500)),
            int(n_classes),
            str(params.get("compute_dtype", "bfloat16")),
            bool(params.get("stage_exit_conv", False)),
        )
        klass, _ = classify_genome_cost(
            cost, int(params.get("batch_size", 128)), n_devices, int(budget))
        if len(_JOB_CLASS_CACHE) >= _JOB_CLASS_CACHE_MAX:
            _JOB_CLASS_CACHE.clear()
        _JOB_CLASS_CACHE[key] = klass
        return klass
    except (TypeError, ValueError):
        # Unevaluable or malformed configs still need a dispatch decision;
        # the worker's evaluator raises the loud error with full context.
        return SIZE_SMALL


def parse_mesh_spec(spec: str) -> Tuple[int, int]:
    """Parse the operator mesh override ``"POPxDATA"`` → ``(pop, data)``.

    Loud ``ValueError`` on anything malformed or non-positive; the worker
    CLI converts it to ``SystemExit``.  Whether the product factors the
    actual device count is checked where the count is known
    (``auto_mesh`` / ``GentunClient._derive_mesh_capacity``), so a stale
    override is re-validated on every :meth:`GentunClient.remesh`.
    """
    parts = str(spec).strip().lower().split("x")
    if len(parts) != 2:
        raise ValueError(
            f"mesh override must be 'POPxDATA' (e.g. '4x2'), got {spec!r}")
    try:
        pop_axis, data_axis = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"mesh override must be 'POPxDATA' with integer axes, got {spec!r}")
    if pop_axis < 1 or data_axis < 1:
        raise ValueError(
            f"mesh override axes must be positive, got {pop_axis}x{data_axis}")
    return pop_axis, data_axis


#: Process-wide operator mesh override (worker ``--mesh POPxDATA``).
#: Consulted by :func:`auto_mesh` when the caller pins no explicit axes,
#: so a worker-level override reaches the evaluator without riding the
#: wire config (cache keys and fitness fingerprints stay untouched).
_MESH_OVERRIDE: Optional[Tuple[int, int]] = None


def set_mesh_override(axes: Optional[Tuple[int, int]]) -> None:
    """Install (or clear, with ``None``) the process-wide mesh override."""
    global _MESH_OVERRIDE
    if axes is not None:
        pop_axis, data_axis = int(axes[0]), int(axes[1])
        if pop_axis < 1 or data_axis < 1:
            raise ValueError(
                f"mesh override axes must be positive, got {pop_axis}x{data_axis}")
        axes = (pop_axis, data_axis)
    _MESH_OVERRIDE = axes


def get_mesh_override() -> Optional[Tuple[int, int]]:
    return _MESH_OVERRIDE


def host_worker_capacity(n_devices: int, slots_per_device: int = 2,
                         size_class: str = SIZE_SMALL,
                         pop_axis: Optional[int] = None,
                         data_axis: Optional[int] = None) -> Tuple[int, int, int]:
    """Derive a host-level worker's capacity from its local device mesh.

    Returns ``(capacity, pop_axis, data_axis)``.  The host (not the chip)
    is the unit of fleet membership: one worker drives every local device
    through the ``(pop, data)`` mesh, and its dispatch window must be a
    shape the compiled evaluator actually wants — so capacity is derived,
    never typed in:

    - start from ``slots_per_device × pop_axis`` (default 2 per device:
      the compile-bucket floor, so even a 1-device host evaluates on the
      stable multi-slot program family);
    - round up to the compile bucket (:func:`pop_bucket`), so a full
      window is one already-cached compile shape;
    - if the bucket shape and the pop-axis size disagree (non-power-of-two
      device counts), step up into the exact-shape regime (≥ 16) and round
      to the next pop-axis multiple — every full window then shards with
      ZERO padding waste.

    Power-of-two hosts land on {2, 4, 8, 16} for 1/2/4/8 devices: always
    a compile bucket AND a pop-axis multiple, so steady-state windows
    never pad and never recompile.

    ``size_class`` derives the per-class window instead: ``big``/``micro``
    jobs run one genome per program on a ``(1, n_devices)`` mesh, so the
    window is exactly 1 — no bucketing, no padding, the frame IS the job.
    Explicit ``pop_axis``/``data_axis`` (the worker's ``--mesh POPxDATA``
    override) replace the heuristic factoring for the small class; their
    product must equal ``n_devices`` (loud ``ValueError`` otherwise, which
    ``remesh()`` re-raises if the device count changed under an override).
    """
    n = int(n_devices)
    if size_class not in SIZE_CLASSES:
        raise ValueError(
            f"size_class must be one of {SIZE_CLASSES}, got {size_class!r}")
    if size_class != SIZE_SMALL:
        return 1, 1, n
    if pop_axis is not None or data_axis is not None:
        if pop_axis is None or data_axis is None:
            raise ValueError(
                "mesh override requires both pop_axis and data_axis")
        pop_axis, data_axis = int(pop_axis), int(data_axis)
        if pop_axis < 1 or data_axis < 1:
            raise ValueError(
                f"mesh override axes must be positive, got {pop_axis}x{data_axis}")
        if pop_axis * data_axis != n:
            raise ValueError(
                f"mesh override {pop_axis}x{data_axis} does not factor "
                f"{n} local devices")
    else:
        pop_axis, data_axis = mesh_factor(n)
    cap = pop_axis * max(1, int(slots_per_device))
    b = pop_bucket(cap)
    if b % pop_axis:
        b = max(16, cap)
        b += (-b) % pop_axis
    return b, pop_axis, data_axis


def auto_mesh(
    pop_size: Optional[int] = None,
    devices: Optional[Sequence[Any]] = None,
    pop_axis: Optional[int] = None,
    data_axis: Optional[int] = None,
    size_class: str = SIZE_SMALL,
) -> Optional["Any"]:
    """Factor the available devices into a ``(pop, data)`` mesh.

    Preference order: put devices on the communication-free ``pop`` axis
    (up to ``pop_size``); spill the rest onto ``data``.  Returns ``None``
    on a single device — the caller then skips sharding entirely, so the
    one-chip path stays annotation-free.

    Explicit ``pop_axis``/``data_axis`` override the heuristic (their
    product must equal the device count; non-positive values are a loud
    ``ValueError`` — ``pop_axis=0`` used to fall into an ``or`` falsy
    trap and silently meant "unset", which is exactly the kind of typo a
    32-device launch script should hear about).  When the caller pins no
    axes, the process-wide operator override (:func:`set_mesh_override`,
    the worker's ``--mesh POPxDATA``) applies; ``size_class`` ``big`` or
    ``micro`` beats both and forces the ``(1, n)`` narrow-pop mesh so the
    batch shards across every device.
    """
    import jax  # deferred: the rest of this module stays jax-free
    from jax.sharding import Mesh

    # Validate explicit overrides BEFORE the single-device early return:
    # a typo like pop_axis=0 must be loud on every topology, not only
    # where it happens to reach the factoring math.
    for name, axis in (("pop_axis", pop_axis), ("data_axis", data_axis)):
        if axis is not None and axis < 1:
            raise ValueError(
                f"{name} must be a positive integer, got {axis} "
                f"(omit the argument to let auto_mesh factor the "
                f"devices itself)")
    if size_class not in SIZE_CLASSES:
        raise ValueError(
            f"size_class must be one of {SIZE_CLASSES}, got {size_class!r}")
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n == 1:
        return None
    if size_class != SIZE_SMALL:
        pop_axis, data_axis = 1, n
    elif pop_axis is None and data_axis is None and _MESH_OVERRIDE is not None:
        pop_axis, data_axis = _MESH_OVERRIDE
    if pop_axis is not None or data_axis is not None:
        if pop_axis is None:
            pop_axis = n // data_axis
        elif data_axis is None:
            data_axis = n // pop_axis
        if pop_axis * data_axis != n:
            raise ValueError(f"pop_axis*data_axis = {pop_axis}*{data_axis} != {n} devices")
    else:
        pop_axis, data_axis = mesh_factor(n, pop_size)
    mesh_devices = np.asarray(devices).reshape(pop_axis, data_axis)
    return Mesh(mesh_devices, axis_names=("pop", "data"))


def mesh_axis_sizes(mesh: Optional["Any"]) -> Tuple[int, int]:
    if mesh is None:
        return 1, 1
    return mesh.shape["pop"], mesh.shape["data"]


def pad_population(genomes: Sequence[Any], multiple: int) -> Tuple[List[Any], int]:
    """Pad the genome list to a multiple of the pop-axis size.

    Padding repeats the last genome; callers slice the results back to the
    original length.  Returns (padded_list, original_length).
    """
    n = len(genomes)
    if multiple <= 1 or n % multiple == 0:
        return list(genomes), n
    padded = list(genomes) + [genomes[-1]] * (multiple - n % multiple)
    return padded, n
