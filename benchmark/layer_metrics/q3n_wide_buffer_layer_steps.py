"""How often the expert layer fell back to its worst-case row buffer in the
window: routed layers times train steps that took the wide height, summed over
the window's ``fetch`` spans (``wide_buffer``, what feeds the counter
``row_buffer_wide_total``).  A program without that attribute reports nothing."""
import q3n_spans


def read(run):
    counts = [r["attrs"]["wide_buffer"] for r in q3n_spans.device_spans(run, "fetch") if "wide_buffer" in r["attrs"]]
    return sum(counts) if counts else None
