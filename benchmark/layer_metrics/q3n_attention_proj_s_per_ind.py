"""Self time of ``attention_proj`` (the gated attention layer outside its core: the q projection with its gate, the
k, v and output projections, the q/k norm and the partial rope, the sigmoid gate on the core's output) in the train
and eval programs, per individual traced."""
import q3n_spans


def read(run):
    return q3n_spans.class_seconds_per_individual(run, ("attention_proj",))
