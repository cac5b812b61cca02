"""Plain reference of the ``lfm2_moe`` family: one expert-parallel rank's share
of LFM2-24B-A2B (https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json,
``model_type`` ``lfm2_moe``) in straightforward ``jax.numpy``, float32, every
product under ``jax.default_matmul_precision("highest")``.  Imports nothing of
``gentun_tpu`` and takes nothing it has made.

Layer ``l``, input ``x`` (tokens, hidden); ``m`` is the configuration's ``model`` block::

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))              norm_eps, weight per channel
    conv:            B, C, u = split3(x W_in);  (C * causal_depthwise_conv1d(B * u)) W_out
                     kernel (channels, L): tap j multiplies the input L-1-j positions back, no bias
    full_attention:  q, k, v = x W_q, x W_k, x W_v; RMSNorm over the head size on q and on k (one weight
                     per position of the head, shared by the heads); rope (rotate-half, theta) on q, k;
                     one causal softmax(q k' / sqrt(head size)) over the full score array of a sequence,
                     each key-value head serving heads / kv_heads query heads; W_o
    dense (l < num_dense_layers):  (silu(x W_1) * x W_3) W_2
    routed:          s = sigmoid(x W_r) over ALL num_experts; top-k of (s + b); w = s[chosen] / (sum + 1e-6);
                     out = sum over the HELD experts e of  [e chosen] w_e (silu(x W1_e) * x W3_e) W2_e
                     -- a loop over the held experts with a 0/1 mask; no sort, no grouped product; what the
                     absent experts would add is left out, and that partial sum goes on
    output:          RMSNorm, logits = x E' over the held rows E of the tied embedding; next-token cross-entropy

Training: mean loss over the batch's tokens, gradients by ``jax.grad``, AdamW
written out (beta1 0.9, eps 1e-8, decoupled decay on everything but the norm
weights, bias-corrected moments, linear warm-up over ``warmup_frac *
train_steps`` steps then constant), and the router bias's rule after each
step: ``b_e += bias_step * sign(mean load - load_e)`` over all experts, the
load counted over the batch (arXiv:2408.15664).

Departures from "one array at a time", all of them only so that the published
widths fit a 16 GB chip beside nothing else, none of them a change of
arithmetic: a batch is taken a sequence at a time and the gradients added up;
each layer, each held expert and each key-value head's attention (a full
score array of its ``heads / kv_heads`` query heads) is under
``jax.checkpoint``, so its interior is computed again in the backward pass and
not kept; the loops over the held experts and over the key-value heads are
``lax.scan`` / ``lax.map``, so that the compiler sees each body once; AdamW's two moments live
on the host between steps and the update runs leaf by leaf.  The caller frees
the program's state first.

``control="fp8"`` rounds both inputs of every product to float8 e4m3 (the
nearest precision below the configuration's bfloat16): the reference itself in
a lower precision, put in the program's place by the check to show that its
limits would catch one.

The weight tree mirrors the program's parameter tree name for name (``embed``,
``final_norm``, ``layers[i]`` with ``op_norm``, ``ffn_norm`` and ``conv`` or
``attn``, ``dense`` or ``moe``), every matrix as (inputs, outputs): a contract
of shapes, stated here and in ``models/lfm2_moe.py::param_shapes``, not an import.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BETA1, ADAM_EPS, ROUTE_EPS, INIT_STD = 0.9, 1e-8, 1e-6, 0.02


def seeded_weights(m: Dict[str, Any], seed: int, std: float = INIT_STD) -> Dict[str, Any]:
    """Weights from the seed, numpy float32: normal(0, std), 0.02 at the
    published widths (a rehearsal's narrow layers take a larger one, or their
    outputs vanish beside the residual); norm weights 1 + normal(0, std), so
    that a norm weight applied wrongly shows; the short convolution's taps
    normal(0, 10 std), so that the operator is not lost beside the residual."""
    rng = np.random.default_rng([seed, 0x1F32])
    h, hd = m["hidden_size"], m["hidden_size"] // m["num_attention_heads"]
    nh, nkv, held = m["num_attention_heads"], m["num_key_value_heads"], m["held_experts"][1] - m["held_experts"][0]
    mat = lambda *shape: (std * rng.standard_normal(shape, dtype=np.float32))
    norm = lambda n: (1.0 + mat(n)).astype(np.float32)
    layers = []
    for i, kind in enumerate(m["layer_types"]):
        layer: Dict[str, Any] = {"op_norm": norm(h), "ffn_norm": norm(h)}
        if kind == "conv":
            layer["conv"] = {"in_proj": mat(h, 3 * h), "kernel": mat(h, m["conv_L_cache"]) * 10, "out_proj": mat(h, h)}
        else:
            layer["attn"] = {"q": mat(h, nh * hd), "k": mat(h, nkv * hd), "v": mat(h, nkv * hd), "o": mat(nh * hd, h),
                             "q_norm": norm(hd), "k_norm": norm(hd)}
        if i < m["num_dense_layers"]:
            f = m["intermediate_size"]
            layer["dense"] = {"w1": mat(h, f), "w3": mat(h, f), "w2": mat(f, h)}
        else:
            f = m["moe_intermediate_size"]
            layer["moe"] = {"router": mat(h, m["num_experts"]), "w1": mat(held, h, f), "w3": mat(held, h, f),
                            "w2": mat(held, f, h)}
        layers.append(layer)
    return {"embed": mat(m["vocab_size"], h), "final_norm": norm(h), "layers": layers}


def _rounder(control: Optional[str]):
    if control is None:
        return lambda a: a
    if control != "fp8":
        raise ValueError(f"unknown control {control!r}")
    return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """x (length, heads, head size): rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def conv_op(w, x, m, rd):
    length = x.shape[0]
    gate_b, gate_c, u = jnp.split(rd(x) @ rd(w["in_proj"]), 3, axis=-1)
    z = gate_b * u
    taps = m["conv_L_cache"]
    y = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j
        y = y + w["kernel"][:, j] * jnp.concatenate([jnp.zeros((back, z.shape[1]), z.dtype), z[:length - back]], 0)
    return rd(gate_c * y) @ rd(w["out_proj"])


def attention(w, x, m, rd):
    length, h = x.shape
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd, per = h // nh, nh // nkv
    q = (rd(x) @ rd(w["q"])).reshape(length, nh, hd)
    k = (rd(x) @ rd(w["k"])).reshape(length, nkv, hd)
    v = (rd(x) @ rd(w["v"])).reshape(length, nkv, hd)
    q = rope(rms_norm(q, w["q_norm"], m["norm_eps"]), m["rope_parameters"]["rope_theta"])
    k = rope(rms_norm(k, w["k_norm"], m["norm_eps"]), m["rope_parameters"]["rope_theta"])
    causal = jnp.tril(jnp.ones((length, length), bool))

    @jax.checkpoint
    def one_kv_head(qkv):  # qg (length, per, hd): the query heads this key-value head serves
        qg, kg, vg = qkv
        scores = jnp.einsum("qgd,kd->gqk", rd(qg), rd(kg)) / math.sqrt(hd)
        prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", rd(prob), rd(vg))

    # one key-value head at a time (a loop, so that the compiler sees its body once)
    out = jax.lax.map(one_kv_head, (q.reshape(length, nkv, per, hd).swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return rd(out.swapaxes(0, 1).reshape(length, nh * hd)) @ rd(w["o"])


def swiglu(x, w1, w3, w2, rd):
    return rd(jax.nn.silu(rd(x) @ rd(w1)) * (rd(x) @ rd(w3))) @ rd(w2)


def routed_ffn(w, bias, x, m, rd):
    """(the held experts' part of the sum, the load of ALL experts)."""
    scores = jax.nn.sigmoid(rd(x) @ rd(w["router"]))
    _, chosen = jax.lax.top_k(scores + bias, m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS)
    first, last = m["held_experts"]

    @jax.checkpoint
    def add_expert(out, expert):  # every token through the expert; the 0/1 mask keeps the tokens that chose it
        e, w1, w3, w2 = expert
        mine = (chosen == e).astype(x.dtype)  # (tokens, k) 0/1
        return out + (mine * weight).sum(-1, keepdims=True) * swiglu(x, w1, w3, w2, rd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(first, last), w["w1"], w["w3"], w["w2"]))
    load = (chosen[..., None] == jnp.arange(m["num_experts"])).sum((0, 1))
    return out, load


def layer(m, index: int, rd, w, bias, x):
    normed = rms_norm(x, w["op_norm"], m["norm_eps"])
    h = x + (conv_op(w["conv"], normed, m, rd) if m["layer_types"][index] == "conv"
             else attention(w["attn"], normed, m, rd))
    normed = rms_norm(h, w["ffn_norm"], m["norm_eps"])
    if index < m["num_dense_layers"]:
        return h + swiglu(normed, w["dense"]["w1"], w["dense"]["w3"], w["dense"]["w2"], rd), None
    out, load = routed_ffn(w["moe"], bias, normed, m, rd)
    return h + out, load


def forward(m, weights, bias, tokens, control: Optional[str] = None):
    """One sequence: (logits (length, held vocabulary), load (routed layers, experts))."""
    rd = _rounder(control)
    x = weights["embed"][tokens]
    loads = []
    for i, w in enumerate(weights["layers"]):
        routed = i >= m["num_dense_layers"]
        x, load = jax.checkpoint(functools.partial(layer, m, i, rd))(
            w, bias[i - m["num_dense_layers"]] if routed else None, x)
        if routed:
            loads.append(load)
    x = rms_norm(x, weights["final_norm"], m["norm_eps"])
    return rd(x) @ rd(weights["embed"]).T, jnp.stack(loads)


def token_loss(logits, targets):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]


@functools.lru_cache(maxsize=None)
def _compiled(model_key: str, control: Optional[str]):
    import json

    m = json.loads(model_key)

    def sequence_loss(weights, bias, x, y):
        logits, load = forward(m, weights, bias, x, control)
        loss = token_loss(logits, y)
        return loss.mean(), (load, loss)

    grad = jax.jit(jax.value_and_grad(sequence_loss, has_aux=True))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=0)
    return grad, add


def _programs(m, control):
    import json

    return _compiled(json.dumps(m, sort_keys=True), control)


@functools.partial(jax.jit, static_argnames=("decay",), donate_argnums=(0, 1, 2))
def _adamw_leaf(p, mom, var, g, lr, beta2, weight_decay, t, decay: bool):
    mom = BETA1 * mom + (1.0 - BETA1) * g
    var = beta2 * var + (1.0 - beta2) * g * g
    update = (mom / (1.0 - BETA1 ** t)) / (jnp.sqrt(var / (1.0 - beta2 ** t)) + ADAM_EPS)
    return p - lr * (update + (weight_decay * p if decay else 0.0)), mom, var


def eval_token_loss(m, weights, bias, x: np.ndarray, y: np.ndarray, control: Optional[str] = None) -> np.ndarray:
    """Loss per token (sequences, length) of held-out sequences."""
    with jax.default_matmul_precision("highest"):
        grad = _programs(m, control)[0]  # the one compiled program; its gradients are not looked at here
        weights = jax.tree_util.tree_map(jnp.asarray, weights)
        return np.stack([np.asarray(grad(weights, bias, jnp.asarray(xs), jnp.asarray(ys))[0][1][1]) for xs, ys in zip(x, y)])


def train(m, weights, batches: Sequence[Tuple[np.ndarray, np.ndarray]], genes: Dict[str, float],
          control: Optional[str] = None, bias: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """AdamW steps from ``weights`` over ``batches`` (each (x, y) of whole
    sequences), the first step numbered 0, from the router bias ``bias`` (zeros if None).  Returns the weights (on the
    device), AdamW's first moment after the last step (on the host), the router
    bias, each step's loss and each step's load (routed layers, experts).
    ``m["train_steps"]`` sets the warm-up's length."""
    with jax.default_matmul_precision("highest"):
        grad, add = _programs(m, control)
        weights = jax.tree_util.tree_map(jnp.asarray, weights)
        n_routed = len(m["layer_types"]) - m["num_dense_layers"]
        bias = jnp.zeros((n_routed, m["num_experts"]), jnp.float32) if bias is None else jnp.asarray(bias)
        paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(weights)[0]]
        moments: Optional[List[Tuple[np.ndarray, np.ndarray]]] = None
        losses, loads = [], []
        for step, (xb, yb) in enumerate(batches):
            total = load = None
            loss = 0.0
            for xs, ys in zip(xb, yb):  # a sequence at a time, gradients added up
                (value, (seq_load, _)), g = grad(weights, bias, jnp.asarray(xs), jnp.asarray(ys))
                total = g if total is None else add(total, g)
                load = seq_load if load is None else load + seq_load
                loss += float(value) / len(xb)
            t = float(step + 1)
            lr = 10.0 ** genes["log10_lr"] * min(1.0, t / max(genes["warmup_frac"] * m["train_steps"], 1.0))
            leaves, tree = jax.tree_util.tree_flatten(weights)
            grads = jax.tree_util.tree_leaves(total)
            del weights, total
            if moments is None:
                moments = [(np.zeros(l.shape, np.float32), np.zeros(l.shape, np.float32)) for l in leaves]
            for i, path in enumerate(paths):  # leaf by leaf; the moments rest on the host
                p, mom, var = _adamw_leaf(leaves[i], jnp.asarray(moments[i][0]), jnp.asarray(moments[i][1]),
                                          grads[i] / len(xb), lr, genes["beta2"], genes["weight_decay"], t,
                                          decay="norm" not in str(path[-1]))
                leaves[i], grads[i] = p, None
                moments[i] = (np.asarray(mom), np.asarray(var))
            weights = jax.tree_util.tree_unflatten(tree, leaves)
            mean_load = len(xb) * xb.shape[1] * m["num_experts_per_tok"] / m["num_experts"]
            bias = bias + genes["bias_step"] * jnp.sign(mean_load - load.astype(jnp.float32))
            losses.append(loss)
            loads.append(np.asarray(load))
        moment = jax.tree_util.tree_unflatten(tree, [mom for mom, _ in moments])
        return {"weights": weights, "moment": moment, "bias": bias, "losses": losses, "loads": loads}
