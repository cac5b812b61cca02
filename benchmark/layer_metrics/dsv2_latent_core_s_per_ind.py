"""Self time of ``latent_core`` (latent attention's causal core: the fused
kernel's calls, or the blockwise core's products, and what sits around them)
in the train and eval programs, per individual traced."""
import dsv2_spans


def read(run):
    return dsv2_spans.class_seconds_per_individual(run, ("latent_core",))
