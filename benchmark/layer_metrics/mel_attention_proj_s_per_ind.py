"""Self time of ``attention_proj`` (either attention outside its core: the q,
k, v and output projections and the rope of the layer's type) in the train and
eval programs, per individual traced."""
import mel_spans


def read(run):
    return mel_spans.class_seconds_per_individual(run, ("attention_proj",))
