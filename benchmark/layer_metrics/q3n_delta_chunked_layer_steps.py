"""Linear-attention layers times train steps whose delta core ran as the chunked scan, per individual (the mean of
``linear_core_layer_steps_chunked`` over the window's ``train`` spans: what feeds
``linear_core_layer_steps_total{program="chunked"}``; 24 in the cell).  A program without the attribute reports nothing."""
import q3n_spans


def read(run):
    return q3n_spans.span_mean(run, "linear_core_layer_steps_chunked")
