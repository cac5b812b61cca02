"""Plain float32 reference of the Genetic-CNN fitness training.

Imports nothing of ``gentun_tpu`` and takes nothing it has made.  One genome
at a time, the decoded DAG built directly: absent nodes are not computed; no
masks, no ``vmap``, no flax, no optax.  Everything runs in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 convolution
otherwise runs in bfloat16 passes).

Recipe followed (``MaskedGeneticCnn``'s documented stage recipe, itself the
rebuild's reading of Xie & Yuille, "Genetic CNN", ICCV 2017):

- a stage's bit-string lists the edges i -> j (i < j) grouped by target node;
  a node with no edge is dropped; a kept node without predecessors is fed by
  the stage's default input node, one without successors feeds the default
  output node; several inputs are summed;
- default input node = Conv3x3(F_s)+ReLU of the stage input; every kept node
  = Conv3x3(F_s)+ReLU of its summed inputs; the default output node is the
  bare sum (no convolution of its own); a stage with no kept node passes the
  default input node through; 2x2 max-pool closes each stage;
- head: Dense(dense_units)+ReLU, dropout, Dense(n_classes);
- loss: mean softmax cross-entropy; SGD with momentum (trace = g + m*trace,
  step = -lr*trace), one learning rate per epoch group.

Departures, each because the number compared would otherwise not be the same
function of the same inputs:

1. The parameters are a dict named as the system names them
   (``stage{s}_entry``, ``stage{s}_node{j}``, ``Dense_0``, ``Dense_1``, each
   with ``kernel`` and ``bias``) so one seeded tree can be handed to both.
2. The dropout mask of step t is drawn as the system draws it: the slot's key
   is split once per step and the second half is folded with the SHA-1 of the
   dropout layer's path and call count, which is how flax names its streams
   (``dropout_key`` below re-implements that one derivation; rate and
   rescaling are plain inverted dropout).
3. ``quantize`` (the control only) rounds the inputs and the kernel of every
   convolution and matrix product to a lower precision on the way in; the
   products still accumulate in float32 and the backward pass sees the
   rounding as the identity.
"""

from __future__ import annotations

import hashlib
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Dict[str, Any]]


# -- the DAG ---------------------------------------------------------------


def decode_stage(bits: Sequence[int], k: int) -> Dict[str, Any]:
    """Kept nodes, each with its predecessors, and which feed the output."""
    bits = [int(b) for b in bits]
    if len(bits) != k * (k - 1) // 2:
        raise ValueError(f"a stage of {k} nodes has {k * (k - 1) // 2} bits, got {len(bits)}")
    preds: Dict[int, List[int]] = {j: [] for j in range(k)}
    succs: Dict[int, List[int]] = {j: [] for j in range(k)}
    pos = 0
    for j in range(1, k):
        for i in range(j):
            if bits[pos]:
                preds[j].append(i)
                succs[i].append(j)
            pos += 1
    kept = [j for j in range(k) if preds[j] or succs[j]]
    return {"kept": kept, "preds": {j: preds[j] for j in kept},
            "exits": [j for j in kept if not succs[j]]}


def decode_genome(genes: Mapping[str, Sequence[int]], nodes: Sequence[int]) -> List[Dict[str, Any]]:
    return [decode_stage(genes[f"S_{s + 1}"], k) for s, k in enumerate(nodes)]


# -- the network -----------------------------------------------------------


def _straight_through(x, quantize):
    return x if quantize is None else x + jax.lax.stop_gradient(quantize(x) - x)


def _conv(x, layer, quantize):
    y = jax.lax.conv_general_dilated(
        _straight_through(x, quantize), _straight_through(layer["kernel"], quantize),
        window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + layer["bias"])


def _max_pool(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def dropout_key(step_key):
    """The key flax's ``Dropout_0`` layer draws its first mask from."""
    digest = hashlib.sha1(b"Dropout_0" + (1).to_bytes(1, "big")).digest()
    return jax.random.fold_in(step_key, jnp.uint32(int.from_bytes(digest[:4], "big")))


def hidden(params: Params, x, dag: List[Dict[str, Any]], quantize: Optional[Callable] = None):
    """The head's hidden layer (n, dense_units), after its ReLU: what the last
    projection reads."""
    h = x.astype(jnp.float32)
    for s, stage in enumerate(dag):
        a0 = _conv(h, params[f"stage{s}_entry"], quantize)
        outs: Dict[int, Any] = {}
        for j in stage["kept"]:
            inputs = [outs[i] for i in stage["preds"][j]] or [a0]
            outs[j] = _conv(sum(inputs[1:], inputs[0]), params[f"stage{s}_node{j}"], quantize)
        exits = [outs[j] for j in stage["exits"]]
        h = _max_pool(sum(exits[1:], exits[0]) if exits else a0)
    h = h.reshape(h.shape[0], -1)
    d0 = params["Dense_0"]
    return jax.nn.relu(_straight_through(h, quantize) @ _straight_through(d0["kernel"], quantize)
                       + d0["bias"])


def forward(params: Params, x, dag: List[Dict[str, Any]], drop_key=None,
            dropout_rate: float = 0.5, quantize: Optional[Callable] = None):
    """Logits (n, classes) of one genome's network, float32."""
    h = hidden(params, x, dag, quantize)
    if drop_key is not None and dropout_rate > 0.0:
        keep = 1.0 - dropout_rate
        mask = jax.random.bernoulli(drop_key, p=keep, shape=h.shape)
        h = jnp.where(mask, h / keep, 0.0)
    # The system keeps its last projection in float32 whatever it computes in.
    d1 = params["Dense_1"]
    return h @ d1["kernel"] + d1["bias"]


def loss_fn(params, x, y, dag, drop_key, dropout_rate, quantize):
    logits = forward(params, x, dag, drop_key, dropout_rate, quantize)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


# -- training and scoring ----------------------------------------------------
#
# Nothing below is jitted.  Op by op every convolution is a small program of
# its own, shared by all nodes of a stage and by every genome; jitted whole, a
# float32 "highest" train step is a 66 MB TPU executable per genome that takes
# half a minute to compile and does not fit a bounded compile cache (PERF.md).


def train(params: Params, genes, nodes, x_full, y_full, batch_idx, key, *,
          learning_rate: Sequence[float], epoch_steps: Sequence[int], momentum: float,
          dropout_rate: float, quantize: Optional[Callable] = None
          ) -> Tuple[Params, Params, np.ndarray]:
    """SGD-with-momentum over ``batch_idx`` (steps, batch) rows of the data.

    ``epoch_steps[g]`` steps run at ``learning_rate[g]``.  Returns the trained
    parameters, the momentum trace after the last step and each step's loss.
    """
    dag = decode_genome(genes, nodes)
    rates = np.concatenate([np.full(n, r, np.float32)
                            for n, r in zip(epoch_steps, learning_rate)])[: len(batch_idx)]
    losses = []
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(jnp.asarray, params)
        trace = jax.tree.map(jnp.zeros_like, p)
        for idx, lr in zip(batch_idx, rates):
            key, sub = jax.random.split(key)
            rows = jnp.asarray(idx)
            loss, g = jax.value_and_grad(loss_fn)(
                p, x_full[rows], y_full[rows], dag, dropout_key(sub), dropout_rate, quantize)
            trace = jax.tree.map(lambda t, gg: gg + momentum * t, trace, g)
            p = jax.tree.map(lambda pp, t: pp - float(lr) * t, p, trace)
            losses.append(loss)
    return p, trace, np.asarray([float(v) for v in losses])


def hidden_rows(params: Params, genes, nodes, x_full, rows, block: int = 256,
                quantize: Optional[Callable] = None) -> np.ndarray:
    """``hidden`` of ``rows`` of the data, ``block`` rows at a time (the last
    block is filled up with its first row and cut again)."""
    dag = decode_genome(genes, nodes)
    rows = np.asarray(rows)
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(rows), block):
            part = rows[s:s + block]
            idx = jnp.asarray(np.concatenate([part, np.full(block - len(part), part[0])]))
            out.append(np.asarray(hidden(params, x_full[idx], dag, quantize))[: len(part)])
    return np.concatenate(out)


def fit_head(h: np.ndarray, y: np.ndarray, n_classes: int, ridge: float = 1e-2) -> Dict[str, np.ndarray]:
    """A last projection fitted to hidden rows ``h`` and their labels by ridge
    least squares on one-hot targets (float64 on the host, returned float32).

    Nineteen train steps leave a network near chance, where an accuracy says
    nothing about the pass that computed it.  A fitted head puts the same
    network far from chance with every class in use, so a wrong validation
    pass has something to get wrong.
    """
    a = np.concatenate([np.asarray(h, np.float64), np.ones((len(h), 1))], axis=1)
    t = np.eye(n_classes)[np.asarray(y)] - 1.0 / n_classes
    gram = a.T @ a
    gram += ridge * np.trace(gram) / len(gram) * np.eye(len(gram))
    w = np.linalg.solve(gram, a.T @ t)
    return {"kernel": w[:-1].astype(np.float32), "bias": w[-1].astype(np.float32)}


def head_classes(h: np.ndarray, head: Dict[str, np.ndarray]) -> np.ndarray:
    """The class the head puts first for each hidden row."""
    return np.argmax(np.asarray(h, np.float64) @ head["kernel"].astype(np.float64)
                     + head["bias"].astype(np.float64), axis=1)


# -- seeded parameters ---------------------------------------------------------


def seeded_params(seed: int, nodes: Sequence[int], filters: Sequence[int], dense_units: int,
                  n_classes: int, input_shape: Sequence[int]) -> Params:
    """One genome's parameter tree from a seed (numpy, float32).

    LeCun-normal kernels (variance 1/fan-in, the scale the system itself trains from), small normal biases so no gradient is
    identically zero by symmetry.  Made by the benchmark, not by the system:
    the same tree is handed to the system's compiled step and to ``train``.
    """
    rng = np.random.default_rng(seed)

    def layer(shape, fan_in):
        return {"kernel": (rng.standard_normal(shape) * np.sqrt(1.0 / fan_in)).astype(np.float32),
                "bias": (0.01 * rng.standard_normal(shape[-1])).astype(np.float32)}

    h, w, c = input_shape
    out: Params = {}
    for s, (k, f) in enumerate(zip(nodes, filters)):
        out[f"stage{s}_entry"] = layer((3, 3, c, f), 9 * c)
        for j in range(k):
            out[f"stage{s}_node{j}"] = layer((3, 3, f, f), 9 * f)
        h, w, c = h // 2, w // 2, f
    out["Dense_0"] = layer((h * w * c, dense_units), h * w * c)
    out["Dense_1"] = layer((dense_units, n_classes), dense_units)
    return out


# -- the control's rounding ------------------------------------------------------


def quantizer(name: Optional[str]) -> Optional[Callable]:
    """Rounding of matmul/conv inputs for the control: None, bfloat16 or fp8."""
    if name in (None, "float32"):
        return None
    dtype = {"bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[name]
    return lambda x: x.astype(dtype).astype(jnp.float32)
