"""The balance term of the window's individuals: the mean of ``aux_loss`` over the
window's ``fetch`` spans (a routed layer's and step's mean of sum_e f_e P_e
before its weight, summed on the device in the train state; 1.0 where routing
is even).  A program without that attribute reports nothing."""
import q3n_spans


def read(run):
    terms = [r["attrs"]["aux_loss"] for r in q3n_spans.device_spans(run, "fetch") if "aux_loss" in r["attrs"]]
    return sum(terms) / len(terms) if terms else None
