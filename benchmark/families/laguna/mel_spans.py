"""What the readers of this family's cell share: the model's fenced device
spans (those that carry ``scope_rules.SPAN_ATTR``), the rows routed to the held
experts as the ``fetch`` spans report them, what the ``train`` spans say of each
mask's kernel and heads, and op-class seconds summed over the family's programs.

**Why this file has the name it has.**  ``BENCHMARK.json`` holds 128 per-layer
metrics, the most a manifest may hold, so this family's cell brings no metric of
its own: it is appended to the ``workloads`` of the accepted metrics of the
routed cell with mixed window and full attention (``layer_metrics/mel_*.py``),
whose quantities are this cell's too (a windowed core and a full core under one
fused kernel, their projections, routing, the grouped products, the optimizer,
the spans).  Those readers find their helper by bare name -- ``import
mel_spans`` -- in the directory of the one family a process loads
(``run.py::load_family``): here that is this file, which gives them this
family's spans, rules and counts (query heads by layer among them).  What this
architecture adds and no accepted reader reads (the gate a head, the dense layer,
the shared expert: ``scope_rules.CLASSES``) is on the traced run's ``info
op_class`` lines, and the window's visible share on the core's ``info`` line."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import scope_reduce
import scope_rules as rules
import spanlib

DEVICE_KINDS = ("train", "eval", "init_params", "compile")


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


def train_attr(run: Dict[str, Any], attr: str) -> Any:
    """The attribute ``attr`` of the window's first ``train`` span that carries it (a program's static facts are the
    same on every one), or None: a program without it reports nothing."""
    return next((r["attrs"][attr] for r in device_spans(run, "train") if attr in r["attrs"]), None)


def kernel_layer_steps(run: Dict[str, Any], mask: str) -> Optional[float]:
    """Layers of ``mask`` ("causal", "window") times train steps whose core ran as the fused kernel, per
    individual: the mean of ``attention_kernel_layer_steps_<mask>`` over the window's ``train`` spans."""
    name = f"attention_kernel_layer_steps_{mask}"
    counts = [r["attrs"][name] for r in device_spans(run, "train") if name in r["attrs"]]
    return sum(counts) / len(counts) if counts else None


def core_visits(run: Dict[str, Any], kind: str) -> Optional[Dict[str, int]]:
    """The block pairs the fused kernel of a ``kind`` layer visits a head and sequence, as the window's ``train``
    spans carry them off the kernel's own table (``attention_kernel_<name>_<mask>``), or None where no span has
    them (the core fell back to XLA's blockwise products, or the program has no such attribute)."""
    import flops

    found = {name: train_attr(run, f"attention_kernel_{name}_{flops.MASK_OF[kind]}")
             for name in ("pairs", "elements", "pairs_bwd", "elements_bwd")}
    return None if None in found.values() else {name: int(n) for name, n in found.items()}


def core_heads(run: Dict[str, Any], kind: str) -> Optional[List[int]]:
    """The query heads of each layer of type ``kind``, as the ``train`` spans state them (``attention_heads_<mask>``)."""
    import flops

    found = train_attr(run, f"attention_heads_{flops.MASK_OF[kind]}")
    return None if found is None else [int(n) for n in found]


def core_roofline_share(run: Dict[str, Any], kind: str) -> Optional[float]:
    """A layer type's core's share of its roofline in the train program: the
    FLOPs of the block pairs its kernel visits (``core_visits``: the kernel's own
    count) times the query heads of the type's layers (``core_heads``: what the
    span states) and its least bytes (``flops.py``: forward kernel twice and
    backward once a layer and step) against the larger of FLOPs / peak and bytes /
    bandwidth (``peaks.json``), over the self time of that type's kernels in the
    traced train steps: the instructions of the type's core class that carry the
    kernels' name (``splash_mqa_*``).  Nothing where the core did not run as the
    kernel: XLA's blockwise products do other work than the count's."""
    import flops
    from family import model_block

    trace, visits, heads = scope_reduce.table(run, rules), core_visits(run, kind), core_heads(run, kind)
    if not trace or not trace.get("individuals") or not run["peak"] or not visits or not heads:
        return None
    klass = rules.CORE_CLASS[kind]
    entries = [p for name, p in trace["programs"].items() if scope_reduce.base_name(name) == rules.TRAIN]
    core = [(op, t) for p in entries for op, (found, t) in p["ops"].items() if found == klass]
    seconds = sum(t for op, t in core if "splash" in op)
    if not seconds:
        return None
    config, n = run["config"], trace["individuals"]
    m = model_block(config)
    sequences = n * config["train_steps"] * config["run"]["batch_sequences"]
    runs = (flops.CORE_FORWARD_RUNS, flops.CORE_BACKWARD_RUNS)
    work = flops.core_flops(m, visits, sequences, *runs, sum(heads))
    moved = flops.core_bytes(m, sequences, config["data"]["seq_len"], *runs, heads)
    by_compute, by_bandwidth = work / run["peak"]["bf16_flops_per_s"], moved / run["peak"]["hbm_bytes_per_s"]
    visible = 100.0 * flops.visible_elements(m, kind, config["data"]["seq_len"]) / visits["elements"]
    print(f"info laguna {klass} roofline: {n} individuals, layers of {heads} query heads, {visits['pairs']} block pairs a "
          f"head forward of which {visible:.1f}% of the score elements are visible, {work / 1e12:.3f} TFLOP, "
          f"{moved / 1e9:.2f} GB, {seconds:.4f} s in the kernels' instructions ({sum(t for _, t in core):.4f} s the whole "
          f"class); bound by {'compute' if by_compute >= by_bandwidth else 'bandwidth'}")
    return 100.0 * max(by_compute, by_bandwidth) / seconds
