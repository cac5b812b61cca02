"""What the readers of this family's cell share: the model's fenced device
spans (those that carry ``scope_rules.SPAN_ATTR``), the rows routed to the held
experts as the ``fetch`` spans report them, what the ``train`` spans say of the
sparse core, and op-class seconds summed over the family's programs.

**Why this file has the name it has, and what reads what.**  ``BENCHMARK.json``
holds 128 per-layer metrics, the most it has been allowed, so this family's cell
brings no metric of its own: it is appended to the ``workloads`` of the accepted
metrics of the routed cell with two attention cores (``layer_metrics/mel_*.py``).
Those readers find their helper by bare name -- ``import mel_spans`` -- in the
directory of the one family a process loads (``run.py::load_family``): here that
is this file.  It maps their two cores onto this architecture's two mechanisms:

- ``mel_full_core_s_per_ind``, ``mel_full_core_roofline_share``,
  ``mel_full_kernel_layer_steps`` read **the masked core** (``sparse_attention/core``:
  every head's attention over the keys the indexer kept);
- ``mel_window_core_s_per_ind``, ``mel_window_core_roofline_share``,
  ``mel_window_kernel_layer_steps`` read **the indexer** (``sparse_attention/
  indexer_scores``, ``select`` and ``indexer_loss``: its scores, the selection, the
  loss pass) -- under a window core's name, for there is no other to give it.

Both ``*_kernel_layer_steps`` read 0: neither runs as a fused kernel in this PR
(XLA's query blocks).  Both roofline shares count the MODEL's work (``flops.py``),
whatever implements it, over the class's self time in the train program.  The
names a ``benchmark`` PR should give these quantities (``sparse_core_*``,
``indexer_*``, ``select_s_per_ind``, ``selection_gap``) are listed in ROADMAP.md;
until then the selection's seconds, the loss pass's and the pairs kept are on
the traced run's ``info`` lines."""

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
    """Layers times train steps whose masked core (``mask`` "causal") or indexer ("window") ran as a fused
    kernel, per individual: the ``train`` spans' ``sparse_core_kernel_layer_steps`` (0 in this PR: XLA's query
    blocks run both), or None where no span says (a program without ``sparse_attention`` layers)."""
    counts = [r["attrs"]["sparse_core_kernel_layer_steps"] for r in device_spans(run, "train")
              if "sparse_core_kernel_layer_steps" in r["attrs"]]
    return sum(counts) / len(counts) if counts else None


def core_visits(run: Dict[str, Any], kind: str) -> Optional[Dict[str, int]]:
    """What the sparse core visits a head and sequence, as the window's ``train`` spans carry it off the core's own
    table (``sparse_core_pairs``, ``sparse_core_elements``); the masked core and the indexer walk the one table."""
    found = {name: train_attr(run, f"sparse_core_{name}") for name in ("pairs", "elements")}
    return None if None in found.values() else {name: int(n) for name, n in found.items()}


def detail_seconds(run: Dict[str, Any], klass: str, program: str = rules.TRAIN) -> Dict[str, float]:
    """Self seconds of ``klass`` in ``program`` by the sub-scope its instructions lie under (``scope_reduce``'s
    ``details``, keyed ``class/detail``)."""
    trace, out = scope_reduce.table(run, rules), {}
    for name, p in ((trace or {}).get("programs") or {}).items():
        if scope_reduce.base_name(name) == program:
            for key, t in p["details"].items():
                if key.split("/")[0] == klass:
                    out[key.partition("/")[2] or klass] = out.get(key.partition("/")[2] or klass, 0.0) + t
    return out


def core_roofline_share(run: Dict[str, Any], kind: str) -> Optional[float]:
    """The share of its roofline, in the train program, of the masked core (``kind`` "full_attention") or of the
    indexer ("sliding_attention"): the MODEL's FLOPs and least bytes (``flops.py``: the pairs the indexer keeps a
    head, or the causal pairs it must score, at the accepted cells' forward and backward runs) against the larger
    of FLOPs / peak and bytes / bandwidth (``peaks.json``), over the self time of the class's instructions in the
    traced train steps, whatever implements them."""
    import flops
    from family import model_block

    trace = scope_reduce.table(run, rules)
    if not trace or not trace.get("individuals") or not run["peak"] or train_attr(run, "sparse_topk") is None:
        return None
    klass = rules.CORE_CLASS[kind]
    seconds = class_seconds(run, (klass,), (rules.TRAIN,))
    if not seconds:
        return None
    config, n = run["config"], trace["individuals"]
    m, length = model_block(config), config["data"]["seq_len"]
    sequences = n * config["train_steps"] * config["run"]["batch_sequences"]
    runs = (flops.CORE_FORWARD_RUNS, flops.CORE_BACKWARD_RUNS)
    if kind == "full_attention":
        pairs = flops.chosen_elements(m, length)
        work, moved = flops.core_flops(m, pairs, sequences, *runs), flops.core_bytes(m, sequences, length, *runs)
    else:
        pairs = flops.causal_elements(length)
        work, moved = flops.indexer_flops(m, pairs, sequences, *runs), flops.indexer_bytes(m, sequences, length, *runs)
    by_compute, by_bandwidth = work / run["peak"]["bf16_flops_per_s"], moved / run["peak"]["hbm_bytes_per_s"]
    visited = (core_visits(run, kind) or {}).get("elements")
    parts = ", ".join(f"{d} {t / n:.4f}" for d, t in sorted(detail_seconds(run, klass).items()))
    selected = [r["attrs"]["selected_pairs"] for r in device_spans(run, "fetch") if "selected_pairs" in r["attrs"]]
    print(f"info keye_vl2 {klass} roofline: {n} individuals, the model's {pairs} pairs a head and sequence "
          f"(the program's blocks visit {visited}), {work / 1e12:.3f} TFLOP, {moved / 1e9:.2f} GB, {seconds:.4f} s in "
          f"the class's instructions (s per individual by scope: {parts}); pairs a layer kept over an individual's "
          f"steps {selected[0] if selected else None}; bound by {'compute' if by_compute >= by_bandwidth else 'bandwidth'}")
    return 100.0 * max(by_compute, by_bandwidth) / seconds
