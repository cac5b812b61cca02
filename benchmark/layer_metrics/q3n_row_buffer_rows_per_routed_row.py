"""How much taller the expert layer's row buffer ran than the rows it served in
the window: the rows of each height times the routed layers x train steps that
took it, over the rows routed to the held experts, both summed over the window's
``fetch`` spans (``row_buffer_heights``: pairs of rows and layer-steps, what
feeds the counter ``row_buffer_height_total{rows}``; ``expert_rows``).  1 where no
pass ran a row nobody routed.  A program without the attribute reports nothing."""
import q3n_spans


def read(run):
    found = [r["attrs"] for r in q3n_spans.device_spans(run, "fetch") if "row_buffer_heights" in r["attrs"]]
    ran = sum(rows * layer_steps for a in found for rows, layer_steps in a["row_buffer_heights"])
    routed = sum(sum(map(sum, a.get("expert_rows", []))) for a in found)
    return ran / routed if routed else None
