"""Self time of ``latent_proj`` (latent attention outside its core: the query,
latent, up and output projections, the latent's norm, rope) in the train and
eval programs, per individual traced."""
import dsv2_spans


def read(run):
    return dsv2_spans.class_seconds_per_individual(run, ("latent_proj",))
