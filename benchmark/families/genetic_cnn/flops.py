"""Analytic conv+dense FLOPs of the Genetic-CNN supergraph, by configuration.

Copy of ``bench.forward_flops_per_image`` / ``bench.schedule_flops`` made to
take the configuration as an argument.  The count is of *executed* work: the
supergraph runs all K_s node convolutions of a stage whatever the masks say,
so masked-out nodes are included (they are executed, not useful).  Pooling,
ReLU, mask multiply-adds, softmax and the optimizer are left out, so a share
of peak worked out from this count is a lower bound on what the chip did.
"""

from __future__ import annotations

from typing import Any, Mapping


def forward_flops_per_image(config: Mapping[str, Any]) -> float:
    """Forward multiply-adds x 2 for ONE image through the whole supergraph."""
    model, data = config["model"], config["data"]
    h, w, c = data["input_shape"]
    flops = 0.0
    for k, f in zip(model["nodes"], model["kernels_per_layer"]):
        flops += 2.0 * h * w * 9 * c * f  # the stage's default input node
        flops += k * 2.0 * h * w * 9 * f * f  # the k supergraph node convs
        h, w, c = h // 2, w // 2, f
    flops += 2.0 * (h * w * c) * model["dense_units"]
    flops += 2.0 * model["dense_units"] * data["n_classes"]
    return flops


def train_span_flops(config: Mapping[str, Any], program_width: int, steps: int) -> float:
    """Executed FLOPs of one train program call: backward counted as 2x forward.

    ``program_width`` is the population axis the program ran with, padding
    slots included: they are executed too.
    """
    return 3.0 * forward_flops_per_image(config) * config["model"]["batch_size"] * steps * program_width
