"""What the readers of this family's cell share: the model's fenced device
spans (those that carry ``scope_rules.SPAN_ATTR``), the rows routed to the held
experts as the ``fetch`` spans report them, what the ``train`` spans say of the
attention kernel and of the Mamba-2 core, and op-class seconds summed over the
family's programs.

**Why this file has the name it has.**  ``BENCHMARK.json`` holds 128 per-layer
metrics, the most a manifest may hold, so this family's cell brings no metric of
its own: it is appended to the ``workloads`` of the accepted metrics of the
routed cell with a scanning mixer beside full attention
(``layer_metrics/q3n_*.py``), whose quantities are this cell's too (a scanning
mixer's projections, convolution and core with the core's share of its roofline;
a full-attention core with its share; the shared expert; the grouped products
with their share; routing; the optimizer; the ladder; the stalls), as
``families/laguna/mel_spans.py`` did for ``mel_*``.  Those readers find their
helper by bare name -- ``import q3n_spans`` -- in the directory of the one family
a process loads (``run.py::load_family``): here that is this file, which gives
them this family's spans, rules and counts.  They ask for ``delta_*`` classes
and ``linear_core_*`` span attributes; ``scope_rules.py`` gives the Mamba-2
mixer's scopes those class names, and :data:`SPAN_NAMES` maps the attributes to
what this architecture's ``train`` spans carry (``state_space_core_*``).  What
this architecture adds and no accepted reader reads (the latent projections:
``scope_rules.CLASSES``) is on the traced run's ``info op_class`` lines; the
cores' and the grouped products' counts are on ``info nemotron_h ... roofline``
lines."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import scope_reduce
import scope_rules as rules
import spanlib

DEVICE_KINDS = ("train", "eval", "init_params", "compile")
#: The span attributes the accepted readers name -> what this architecture's ``train`` spans carry.
SPAN_NAMES = {"linear_core_layer_steps_chunked": "state_space_core_layer_steps_chunked",
              "linear_core_chunk": "state_space_core_chunk"}


def device_spans(run: Dict[str, Any], kind: str, where: str = "window") -> List[Dict[str, Any]]:
    return [r for r in spanlib.spans(run, kind, where) if rules.SPAN_ATTR in (r.get("attrs") or {})]


def per_trained(run: Dict[str, Any], kind: str) -> Optional[float]:
    """Fenced spans of ``kind`` in the window per individual trained."""
    found, n = device_spans(run, kind), spanlib.trained(run)
    return sum(r["dur_s"] for r in found) / n if found and n else None


def routed_rows(run: Dict[str, Any], first: Optional[int] = None) -> float:
    """Rows routed to the held experts by the window's individuals (the first
    ``first`` of them), all routed layers and train steps together."""
    found = sorted(device_spans(run, "fetch"), key=lambda r: r["t_wall"])[:first]
    return float(sum(sum(map(sum, r["attrs"].get("expert_rows", []))) for r in found))


def rows_by_expert(run: Dict[str, Any]) -> List[float]:
    """Rows of each (routed layer, held expert), the window's individuals together."""
    per_individual = [r["attrs"]["expert_rows"] for r in device_spans(run, "fetch") if r["attrs"].get("expert_rows")]
    return [float(sum(cell)) for layer in zip(*per_individual) for cell in zip(*layer)]


def class_seconds(run: Dict[str, Any], classes: Sequence[str], programs: Sequence[str] = rules.PROGRAMS
                  ) -> Optional[float]:
    """Self seconds of ``classes`` over ``programs`` in the traced stretch (all individuals traced)."""
    trace = scope_reduce.table(run, rules)
    if not trace or not trace.get("individuals"):
        return None
    entries = [scope_reduce.merged(trace, p) for p in programs]
    if not any(e["runs"] for e in entries):
        return None
    return sum(e["classes"].get(c, 0.0) for e in entries for c in classes)


def class_seconds_per_individual(run: Dict[str, Any], classes: Sequence[str]) -> Optional[float]:
    seconds = class_seconds(run, classes)
    return None if seconds is None else seconds / run["scope_table"]["individuals"]


def span_mean(run: Dict[str, Any], attr: str) -> Optional[float]:
    """The mean of the ``train`` spans' attribute ``attr`` over the window, per individual (layers times train steps
    whose core ran as a given program: ``attention_kernel_layer_steps_causal``, ``linear_core_layer_steps_chunked``).
    A program without that attribute reports nothing.  An attribute of ``SPAN_NAMES`` is read under this
    architecture's name for it."""
    attr = SPAN_NAMES.get(attr, attr)
    counts = [r["attrs"][attr] for r in device_spans(run, "train") if attr in r["attrs"]]
    return sum(counts) / len(counts) if counts else None


def core_visits(run: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The block pairs the fused kernel of the full-attention layers visits a head and sequence, as the window's
    ``train`` spans carry them off the kernel's own table (``attention_kernel_<name>_causal``), or None where no span
    has them (the core fell back to XLA's blockwise products, or the program has no such attribute)."""
    names = {name: f"attention_kernel_{name}_causal" for name in ("pairs", "elements", "pairs_bwd", "elements_bwd")}
    found = next((r["attrs"] for r in device_spans(run, "train") if all(n in r["attrs"] for n in names.values())), None)
    return {name: int(found[attr]) for name, attr in names.items()} if found else None


def delta_chunk(run: Dict[str, Any]) -> Optional[int]:
    """The positions a chunk of the Mamba-2 core holds, as the ``train`` spans say (``state_space_core_chunk``)."""
    attr = SPAN_NAMES["linear_core_chunk"]
    found = next((r["attrs"][attr] for r in device_spans(run, "train") if attr in r["attrs"]), None)
    return None if found is None else int(found)


def _train_class(run: Dict[str, Any], klass: str):
    """(the traced train programs' instructions of ``klass`` as (op, seconds), the individuals traced) or None."""
    trace = scope_reduce.table(run, rules)
    if not trace or not trace.get("individuals") or not run["peak"]:
        return None
    entries = [p for name, p in trace["programs"].items() if scope_reduce.base_name(name) == rules.TRAIN]
    return [(op, t) for p in entries for op, (found, t) in p["ops"].items() if found == klass], trace["individuals"]


def _share(run: Dict[str, Any], what: str, n: int, work: float, moved: float, seconds: float, note: str) -> float:
    by_compute, by_bandwidth = work / run["peak"]["bf16_flops_per_s"], moved / run["peak"]["hbm_bytes_per_s"]
    print(f"info nemotron_h {what} roofline: {n} individuals, {note}, {work / 1e12:.3f} TFLOP, {moved / 1e9:.2f} GB, "
          f"{seconds:.4f} s traced; bound by {'compute' if by_compute >= by_bandwidth else 'bandwidth'}")
    return 100.0 * max(by_compute, by_bandwidth) / seconds


def full_core_roofline_share(run: Dict[str, Any]) -> Optional[float]:
    """The attention core's share of its roofline in the train program: the FLOPs of the block pairs its kernel
    visits (``core_visits``: the kernel's own count) and its least bytes (``flops.py``: forward kernel twice and
    backward once a layer and step) against the larger of FLOPs / peak and bytes / bandwidth (``peaks.json``), over
    the self time of the kernels in the traced train steps: the instructions of ``full_core`` that carry the kernels'
    name (``splash_mqa_*``).  Nothing where the core did not run as the kernel."""
    import flops
    from family import model_block

    got, visits = _train_class(run, "full_core"), core_visits(run)
    if not got or not visits:
        return None
    seconds = sum(t for op, t in got[0] if "splash" in op)
    if not seconds:
        return None
    config, n = run["config"], got[1]
    m = model_block(config)
    sequences = n * config["train_steps"] * config["run"]["batch_sequences"]
    layers, runs = flops.layers_of(m, "full_attention"), (flops.CORE_FORWARD_RUNS, flops.CORE_BACKWARD_RUNS)
    return _share(run, "attention core", n, layers * flops.core_flops(m, visits, sequences, *runs),
                  layers * flops.core_bytes(m, sequences, config["data"]["seq_len"], *runs), seconds,
                  f"{layers} layers, {visits['pairs']} block pairs a head forward")


def delta_core_roofline_share(run: Dict[str, Any]) -> Optional[float]:
    """The Mamba-2 core's share of its roofline in the train program (the accepted reader's name for a scanning
    mixer's core): the executed chunk arithmetic of the heads held and the least bytes of its operands, chunk states
    and outputs (``flops.py``: forward twice, the transpose once) against the larger of FLOPs / peak and bytes /
    bandwidth, over the self time of everything under ``mamba2/core`` in the traced train steps (the whole class:
    the core is XLA's ops, no one kernel).  Nothing from a program whose ``train`` spans name no chunk."""
    import flops
    from family import model_block

    got, chunk = _train_class(run, "delta_core"), delta_chunk(run)
    if not got or not chunk:
        return None
    seconds = sum(t for _, t in got[0])
    if not seconds:
        return None
    config, n = run["config"], got[1]
    m = model_block(config)
    sequences, length = n * config["train_steps"] * config["run"]["batch_sequences"], config["data"]["seq_len"]
    layers, runs = flops.layers_of(m, "mamba2"), (flops.CORE_FORWARD_RUNS, flops.CORE_BACKWARD_RUNS)
    return _share(run, "mamba2 core", n, layers * flops.state_space_core_flops(m, sequences, length, chunk, *runs),
                  layers * flops.state_space_core_bytes(m, sequences, length, chunk, *runs), seconds,
                  f"{layers} layers of {flops.mamba_held(m)[0]} held heads, chunks of {chunk}")
