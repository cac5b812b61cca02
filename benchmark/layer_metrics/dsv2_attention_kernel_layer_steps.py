"""Whether attention ran as the fused kernel: attention layers times train
steps that lowered to it, per individual -- the mean of
``attention_kernel_layer_steps`` over the window's ``train`` spans (what feeds
the counter ``attention_kernel_layer_steps_total``; 0 where the program fell
back to XLA's blockwise core).  A program without that attribute reports nothing."""
import dsv2_spans


def read(run):
    counts = [r["attrs"]["attention_kernel_layer_steps"] for r in dsv2_spans.device_spans(run, "train")
              if "attention_kernel_layer_steps" in r["attrs"]]
    return sum(counts) / len(counts) if counts else None
