"""Plain reference of the ``laguna`` family: one expert-parallel rank's share of
Laguna-XS.2 (https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json,
``model_type`` ``laguna``) in straightforward ``jax.numpy``, float32, every
product under ``jax.default_matmul_precision("highest")``.  Imports nothing of
``gentun_tpu`` and takes nothing it has made.

Layer ``l`` of type ``t = layer_types[l]`` with ``n_l = num_attention_heads_per_layer[l]`` query heads (48 in a
full layer, 64 in a sliding one), input ``x`` (tokens, hidden); ``m`` is the configuration's model block
(``family.model_block``: the published keys under their published names, the lists cut to the layers kept)::

    h = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))          eps rms_norm_eps, a weight per channel, no bias
    Attn_l:  q = x W_q -> n_l x head_dim;  k = x W_k, v = x W_v -> num_key_value_heads x head_dim (8 x 128 in both
             types);  query head n uses key-value head n // (n_l / kv heads): 6 query heads to a key-value head in a
             full layer, 8 in a sliding one, every head written out (K and V repeated, no grouped product);
             rope_t on the first rotary_t = partial_rotary_factor_t * head_dim columns of q and k, rotate-half inside
             them, pairs (c, c + rotary_t / 2); the other columns pass;
             score[i, j] = q_i . k_j / sqrt(head_dim);  softmax over the keys j with visible_t[i, j] = 1;
             o_head = o_head * sigmoid(x . w_head)     one scalar a head and token, W_g (hidden, n_l), ``x`` the
             layer's normed input as for q;  out W_o
        t = sliding_attention:  visible[i, j] = 1  iff  0 <= i - j <= sliding_window - 1   (the window counts the
             query's own position: 512 keys at most);  rotary 128 (factor 1): inv_freq_c = theta^(-2c/128), theta 1e4
        t = full_attention:     visible[i, j] = 1  iff  j <= i;  rotary 64 (factor 0.5), YaRN over those 64 columns:
             inv_freq_c = (1 - g_c) / (factor * theta^(2c/64)) + g_c / theta^(2c/64),
             g_c = 1 - clip((c - low) / (high - low), 0, 1),  low = floor(d(beta_fast)), high = ceil(d(beta_slow)),
             d(b) = 64 * ln(original_max_position_embeddings / (2 pi b)) / (2 ln theta);
             cos and sin both times attention_factor (1.4158883083359672 = 0.1 ln 64 + 1), so the rotated columns'
             part of a score carries its square and the passing columns' part does not
        The masks are explicit 0/1 arrays built from i and j, a block of queries at a time so that a head's scores
        fit (1,024 x 8,192 at the published length), never a slice of the keys.
    FFN_l, mlp_layer_types[l] = dense (layer 0):  (silu(x W_1) * x W_3) W_2, width intermediate_size
    FFN_l, sparse:  s = sigmoid(x W_r) over ALL num_experts, float32;  chosen = the num_experts_per_tok largest of
             (s + b), b the router's bias (no gradient);  w_e = s_e / (sum over the chosen of s + 1e-6)
             out = moe_routed_scaling_factor * sum over the HELD experts e of [e chosen] w_e (silu(x W1_e) * x W3_e) W2_e
                   -- a loop over the held experts with a 0/1 mask; no sort, no grouped product; what the absent
                   experts would add is left out, and that partial sum goes on --
                 + (silu(x W1_s) * x W3_s) W2_s        the shared expert, unscaled, whole on every rank
    output:  RMSNorm, logits = x H' over the held rows H of the untied head; loss = mean next-token cross-entropy

Departures from the published model, each noted in the configuration's ``assumed``: the gate is read as one scalar a
head (``gating: true`` has no shape; that reading gives the stated 33.4 B parameters); the router is read as the
sigmoid-with-bias rule (the row has no ``scoring_func``); no per-head norm of q and k (the config has no key for
one); rope pairs in the rotate-half layout.

Training: mean loss over the batch's tokens, gradients by ``jax.grad``, AdamW written out (beta1 0.9, eps 1e-8,
decoupled decay on everything but the norm weights, bias-corrected moments, linear warm-up over ``warmup_frac *
train_steps`` steps then constant), and the router bias's rule after each step: ``b_e += bias_step * sign(mean load -
load_e)`` over all experts, the load counted over the batch (arXiv:2408.15664).

Departures from "one array at a time", all of them only so that the published widths fit a 16 GB chip beside the
window's loaded programs, none of them a change of arithmetic: a batch is taken a sequence at a time and the
gradients added up (the loss is a mean over sequences of equal length); each layer, each held expert and each (head,
block of queries) of attention is under ``jax.checkpoint``, so its interior is computed again in the backward pass
and not kept; the loops over the held experts, the heads and the query blocks are ``lax.scan`` / ``lax.map``, so that
the compiler sees each body once; AdamW's two moments live on the host between steps and the update runs leaf by
leaf.  The caller frees the program's state first.

``control="fp8"`` rounds both inputs of every product to float8 e4m3 (the nearest precision below the
configuration's bfloat16): the reference itself in a lower precision, put in the program's place by the check to show
that its limits would catch one.

The weight tree mirrors the program's parameter tree name for name (``embed``, ``head``, ``final_norm``,
``layers[i]`` with ``op_norm``, ``ffn_norm``, ``attn`` (``q``, ``k``, ``v``, ``o``, ``gate``) and ``dense`` (``w1``,
``w3``, ``w2``) or ``moe`` (``router``, ``w1``, ``w3``, ``w2``, ``shared`` (``w1``, ``w3``, ``w2``))), every matrix as
(inputs, outputs): a contract of shapes, stated here and in ``models/lfm2_moe.py::param_shapes``, not an import.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BETA1, ADAM_EPS, ROUTE_EPS, INIT_STD = 0.9, 1e-8, 1e-6, 0.02
#: Queries whose scores against every key are alive at once, a head: 1,024 x 8,192 float32 = 34 MB.
QUERY_BLOCK = 1024


def routed_layers(m: Dict[str, Any]) -> List[int]:
    """The layers kept (by their place among them) whose feed-forward is routed."""
    return [i for i, kind in enumerate(m["mlp_layer_types"]) if kind == "sparse"]


def seeded_weights(m: Dict[str, Any], seed: int, std: float = INIT_STD, router_gain: float = 1.0,
                   embed_std: Optional[float] = None, out_std: Optional[float] = None,
                   gate_std: Optional[float] = None) -> Dict[str, Any]:
    """Weights from the seed, numpy float32: normal(0, std), 0.02 at the
    published widths (a rehearsal's narrow layers take a larger one, or their
    outputs vanish beside the residual); norm weights 1 + normal(0, std), so
    that a norm weight applied wrongly shows; the router ``router_gain`` times
    wider, so that its sigmoids are far from a half.  ``embed_std``,
    ``out_std`` and ``gate_std`` (each ``std`` unless given) are the
    embedding's, the deviation of the matrices that write into the residual
    stream (attention's W_o, the dense layer's, the experts' and the shared
    expert's W2) and the head gates': the configuration's ``check`` says what it
    takes and why."""
    rng = np.random.default_rng([seed, 0x1A60])
    h, hd, held = m["hidden_size"], m["head_dim"], m["held_experts"][1] - m["held_experts"][0]
    nkv, f, fs, fd = (m["num_key_value_heads"], m["moe_intermediate_size"], m["shared_expert_intermediate_size"],
                      m["intermediate_size"])
    embed_std, out_std, gate_std = (std if v is None else v for v in (embed_std, out_std, gate_std))
    mat = lambda *shape, std=std: (std * rng.standard_normal(shape, dtype=np.float32))
    norm = lambda n: (1.0 + mat(n)).astype(np.float32)
    layers = []
    for nh, ffn in zip(m["num_attention_heads_per_layer"], m["mlp_layer_types"]):
        layer: Dict[str, Any] = {"op_norm": norm(h), "ffn_norm": norm(h),
                                 "attn": {"q": mat(h, nh * hd), "k": mat(h, nkv * hd), "v": mat(h, nkv * hd),
                                          "o": mat(nh * hd, h, std=out_std), "gate": mat(h, nh, std=gate_std)}}
        if ffn == "dense":
            layer["dense"] = {"w1": mat(h, fd), "w3": mat(h, fd), "w2": mat(fd, h, std=out_std)}
        else:
            layer["moe"] = {"router": router_gain * mat(h, m["num_experts"]), "w1": mat(held, h, f),
                            "w3": mat(held, h, f), "w2": mat(held, f, h, std=out_std),
                            "shared": {"w1": mat(h, fs), "w3": mat(h, fs), "w2": mat(fs, h, std=out_std)}}
        layers.append(layer)
    return {"embed": mat(m["vocab_size"], h, std=embed_std), "head": mat(m["vocab_size"], h), "final_norm": norm(h),
            "layers": layers}


def _rounder(control: Optional[str]):
    if control is None:
        return lambda a: a
    if control != "fp8":
        raise ValueError(f"unknown control {control!r}")
    return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotary_columns(m: Dict[str, Any], kind: str) -> int:
    """The leading columns of a head that the rope of a ``kind`` layer turns."""
    return int(m["head_dim"] * m["rope_parameters"][kind].get("partial_rotary_factor", 1))


def rope_frequencies(dim: int, r: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """(the ``dim / 2`` rotary frequencies over ``dim`` rotated columns, what cos and sin are multiplied by) of one
    layer type's ``rope_parameters`` block (float64; the equations of the header)."""
    c = np.arange(dim // 2, dtype=np.float64)
    theta = float(r["rope_theta"])
    plain = 1.0 / theta ** (2.0 * c / dim)
    if r.get("rope_type", "default") == "default":
        return plain, 1.0
    assert r["rope_type"] == "yarn", r
    where = lambda beta: dim * math.log(r["original_max_position_embeddings"] / (beta * 2.0 * math.pi)) \
        / (2.0 * math.log(theta))
    low, high = max(math.floor(where(r["beta_fast"])), 0), min(math.ceil(where(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    g = 1.0 - np.clip((c - low) / (high - low), 0.0, 1.0)
    return (1.0 - g) * plain / r["factor"] + g * plain, float(r["attention_factor"])


def rope(x, r: Dict[str, Any], rotary: int):
    """x (length, heads, head size): rotate-half rotary embedding under one layer type's block ``r`` on the leading
    ``rotary`` columns; the others pass as they are."""
    half = rotary // 2
    freq, amplitude = rope_frequencies(rotary, r)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :] * amplitude, jnp.sin(angle)[:, None, :] * amplitude
    x1, x2, passing = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, passing], axis=-1)


def visible(i, j, kind: str, m) -> jnp.ndarray:
    """The 0/1 mask of a layer of type ``kind``: 1 where the query at position ``i`` sees the key at ``j``."""
    back = i - j
    if kind == "sliding_attention":
        return ((back >= 0) & (back <= m["sliding_window"] - 1)).astype(jnp.int32)
    assert kind == "full_attention", kind
    return (back >= 0).astype(jnp.int32)


def attention(w, x, m, kind: str, nh: int, rd):
    """One sequence (length, hidden) through the attention of a layer of type ``kind`` with ``nh`` query heads."""
    length = x.shape[0]
    nkv, hd = m["num_key_value_heads"], m["head_dim"]
    r, rotary = m["rope_parameters"][kind], rotary_columns(m, kind)
    q = rope((rd(x) @ rd(w["q"])).reshape(length, nh, hd), r, rotary)
    k = rope((rd(x) @ rd(w["k"])).reshape(length, nkv, hd), r, rotary)
    v = (rd(x) @ rd(w["v"])).reshape(length, nkv, hd)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)  # head n <- key-value head n // (nh / nkv)
    block = min(QUERY_BLOCK, length)
    assert length % block == 0, (length, block)
    positions = jnp.arange(length)

    def one_head(qkv):
        qh, kh, vh = qkv

        @jax.checkpoint
        def one_block(args):
            qb, ib = args
            mask = visible(ib[:, None], positions[None, :], kind, m)  # (block, length) of 0 and 1
            scores = (rd(qb) @ rd(kh).T) / math.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(mask == 1, scores, -jnp.inf), axis=-1)
            return rd(prob) @ rd(vh)

        return jax.lax.map(one_block, (qh.reshape(-1, block, hd), positions.reshape(-1, block))).reshape(length, hd)

    out = jax.lax.map(one_head, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))  # a head at a time
    if m.get("head_gate", True):  # False: the ungated attention (what the test of the gate takes apart)
        out = out * jax.nn.sigmoid(rd(x) @ rd(w["gate"])).T[:, :, None]  # (heads, length, 1): one scalar a head and token
    return rd(out.swapaxes(0, 1).reshape(length, nh * hd)) @ rd(w["o"])


def swiglu(x, w1, w3, w2, rd):
    return rd(jax.nn.silu(rd(x) @ rd(w1)) * (rd(x) @ rd(w3))) @ rd(w2)


def routed_ffn(w, bias, x, m, rd):
    """One sequence: (the held experts' part of the sum times the scaling factor, plus the shared expert; the load
    of ALL experts)."""
    experts, k = m["num_experts"], m["num_experts_per_tok"]
    scores = jax.nn.sigmoid(rd(x) @ rd(w["router"]))
    _, chosen = jax.lax.top_k(scores + bias, k)  # the bias chooses and weighs nothing
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS)  # over the chosen, held here or not
    first, last = m["held_experts"]

    @jax.checkpoint
    def add_expert(out, expert):  # every token through the expert; the 0/1 mask keeps the tokens that chose it
        e, w1, w3, w2 = expert
        mine = (chosen == e).astype(x.dtype)
        return out + (mine * weight).sum(-1, keepdims=True) * swiglu(x, w1, w3, w2, rd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(first, last), w["w1"], w["w3"], w["w2"]))
    out = m["moe_routed_scaling_factor"] * out
    if m.get("shared_expert", True):  # False: the routed part alone (what the shares-add-up test takes apart)
        shared = w["shared"]
        out = out + swiglu(x, shared["w1"], shared["w3"], shared["w2"], rd)
    return out, (chosen[..., None] == jnp.arange(experts)).sum((0, 1))


def layer(m, index: int, rd, w, bias, x):
    """One sequence through layer ``index`` (of the layers kept): (output, load or None)."""
    kind, nh = m["layer_types"][index], m["num_attention_heads_per_layer"][index]
    h = x + attention(w["attn"], rms_norm(x, w["op_norm"], m["rms_norm_eps"]), m, kind, nh, rd)
    normed = rms_norm(h, w["ffn_norm"], m["rms_norm_eps"])
    if m["mlp_layer_types"][index] == "dense":
        dense = w["dense"]
        return h + swiglu(normed, dense["w1"], dense["w3"], dense["w2"], rd), None
    out, load = routed_ffn(w["moe"], bias, normed, m, rd)
    return h + out, load


def forward(m, weights, bias, tokens, control: Optional[str] = None):
    """One sequence: (logits (length, held vocabulary), load (routed layers, experts)); ``bias`` (routed layers,
    experts)."""
    rd = _rounder(control)
    x = weights["embed"][tokens]
    routed, loads = routed_layers(m), []
    for i, w in enumerate(weights["layers"]):
        x, load = jax.checkpoint(functools.partial(layer, m, i, rd))(w, bias[routed.index(i)] if i in routed else None, x)
        if load is not None:
            loads.append(load)
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    return rd(x) @ rd(weights["head"]).T, jnp.stack(loads)


def token_loss(logits, targets):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]


@functools.lru_cache(maxsize=None)
def _compiled(model_key: str, control: Optional[str]):
    m = json.loads(model_key)

    def sequence_loss(weights, bias, x, y):
        logits, load = forward(m, weights, bias, x, control)
        loss = token_loss(logits, y)
        return loss.mean(), (load, loss)

    grad = jax.jit(jax.value_and_grad(sequence_loss, has_aux=True))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=0)
    return grad, add


def _programs(m, control):
    return _compiled(json.dumps(m, sort_keys=True), control)


@functools.partial(jax.jit, static_argnames=("decay",), donate_argnums=(0, 1, 2))
def _adamw_leaf(p, mom, var, g, lr, beta2, weight_decay, t, decay: bool):
    mom = BETA1 * mom + (1.0 - BETA1) * g
    var = beta2 * var + (1.0 - beta2) * g * g
    update = (mom / (1.0 - BETA1 ** t)) / (jnp.sqrt(var / (1.0 - beta2 ** t)) + ADAM_EPS)
    return p - lr * (update + (weight_decay * p if decay else 0.0)), mom, var


def zero_bias(m) -> np.ndarray:
    return np.zeros((len(routed_layers(m)), m["num_experts"]), np.float32)


def eval_token_loss(m, weights, bias, x: np.ndarray, y: np.ndarray, control: Optional[str] = None) -> np.ndarray:
    """Cross-entropy per token (sequences, length) of held-out sequences under the router bias ``bias``."""
    with jax.default_matmul_precision("highest"):
        grad = _programs(m, control)[0]  # the one compiled program; its gradients are not looked at here
        weights, bias = jax.tree_util.tree_map(jnp.asarray, weights), jnp.asarray(bias)
        return np.stack([np.asarray(grad(weights, bias, jnp.asarray(xs), jnp.asarray(ys))[0][1][1])
                         for xs, ys in zip(x, y)])


def train(m, weights, batches: Sequence[Tuple[np.ndarray, np.ndarray]], genes: Dict[str, float],
          control: Optional[str] = None, bias: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """AdamW steps from ``weights`` over ``batches`` (each (x, y) of whole
    sequences), the first step numbered 0, from the router bias ``bias``
    (zeros if None).  Returns the weights (on the device), AdamW's first moment
    after the last step (on the host), the router bias after its last step,
    each step's loss and its load (routed layers, experts).
    ``m["train_steps"]`` sets the warm-up's length."""
    with jax.default_matmul_precision("highest"):
        grad, add = _programs(m, control)
        weights = jax.tree_util.tree_map(jnp.asarray, weights)
        bias = jnp.asarray(zero_bias(m) if bias is None else bias)
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
        return {"weights": weights, "moment": moment, "bias": np.asarray(bias), "losses": losses, "loads": loads}
