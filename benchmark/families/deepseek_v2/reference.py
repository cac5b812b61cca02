"""Plain reference of the ``deepseek_v2`` family: one expert-parallel rank's share
of DeepSeek-V2-Lite (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json,
``model_type`` ``deepseek_v2``) in straightforward ``jax.numpy``, float32, every
product under ``jax.default_matmul_precision("highest")``.  Imports nothing of
``gentun_tpu`` and takes nothing it has made.

Layer ``l``, input ``x`` (tokens, hidden); ``m`` is the configuration's model block
(``family.model_block``: the published keys under their published names)::

    h = x + MLA(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))                eps rms_norm_eps, weight per channel
    MLA:  q = x W_q                       -> heads x (qk_nope_head_dim + qk_rope_head_dim)   [q_lora_rank null]
          [c ; k_pe] = x W_kva            -> kv_lora_rank + qk_rope_head_dim;   c = RMSNorm(c) (its own weight)
          [k_nope ; v] = c W_kvb          -> heads x (qk_nope_head_dim + v_head_dim)
          q_pe, k_pe = rope(q_pe), rope(k_pe);  k_pe is ONE head, copied to every query head
          score = (q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln factor + 1
          out = concat_heads(causal softmax(score) v) W_o
    rope: rotate-half pairs (i, i + rope/2), YaRN frequencies [rope_scaling]:
          inv_freq_i = (1 - g_i) / (factor * theta^(2i/rope)) + g_i / theta^(2i/rope),  g_i = 1 - clip((i - low) / (high - low), 0, 1),
          low = floor(d(beta_fast)), high = ceil(d(beta_slow)), d(b) = rope * ln(original / (2 pi b)) / (2 ln theta);
          cos and sin times mscale(mscale) / mscale(mscale_all_dim) (1 in the published config)
    dense (l < first_k_dense_replace):  (silu(x W_1) * x W_3) W_2, width intermediate_size
    routed:  p = softmax(x W_r) over ALL n_routed_experts; chosen = top-k of p (greedy; n_group 1, topk_group 1);
             w = p[chosen]  (norm_topk_prob false, routed_scaling_factor 1)
             out = sum over the HELD experts e of [e chosen] w_e (silu(x W1_e) * x W3_e) W2_e
                   -- a loop over the held experts with a 0/1 mask; no sort, no grouped product; what the
                   absent experts would add is left out, and that partial sum goes on --
                 + (silu(x W1_s) * x W3_s) W2_s, the n_shared_experts shared experts as one SwiGLU of width
                   n_shared_experts * moe_intermediate_size, computed whole on every rank
    output:  RMSNorm, logits = x H' over the held rows H of the untied head; next-token cross-entropy
    loss  =  mean cross-entropy + alpha * sum over routed layers of  mean over sequences of  sum_e f_e P_e
             (seq_aux true), over ALL experts: f_e = n_routed_experts / (k L) * #(tokens of the sequence that
             chose e), P_e = mean over the sequence of p_e.  f carries no gradient (a count); the term's
             gradient reaches the router through P and, through the router's input, the layers below, as in the
             published modelling code (``AddAuxiliaryLoss``); inside the expert layer it reaches nothing else

Departures from the published model, each noted in the configuration's ``assumed``: ``alpha`` (``aux_loss_alpha``,
0.001 in the model's own config) is not in the catalog's row and is the recipe's fifth gene; the device-level and
communication balance losses of the DeepSeek-V2 paper are not in the published modelling code with ``n_group``
1 and are left out; rope pairs are in the rotate-half layout (the published code permutes its interleaved
weights into it before rotating: with random weights a permutation of columns, equal scores).

Training: mean loss over the batch's tokens plus the balance term over the batch's sequences, gradients by
``jax.grad``, AdamW written out (beta1 0.9, eps 1e-8, decoupled decay on everything but the norm weights,
bias-corrected moments, linear warm-up over ``warmup_frac * train_steps`` steps then constant).  No router
bias and no rule outside the gradient.

Departures from "one array at a time", all of them only so that the published widths fit a 16 GB chip beside
the window's loaded programs, none of them a change of arithmetic: a batch is taken a sequence at a time and
the gradients added up (both parts of the loss are means over sequences of equal length); each layer, each
held expert and each head's attention (one full (length x length) score array, 67 MB at 4,096) is under
``jax.checkpoint``, so its interior is computed again in the backward pass and not kept; the loops over the
held experts and over the heads are ``lax.scan`` / ``lax.map``, so that the compiler sees each body once;
AdamW's two moments live on the host between steps and the update runs leaf by leaf.  At the published cut:
weights, one gradient tree and a running sum, 3 x 2.54 GB, and ~2 GB of temporaries.  The caller frees the
program's state first.

``control="fp8"`` rounds both inputs of every product to float8 e4m3 (the nearest precision below the
configuration's bfloat16): the reference itself in a lower precision, put in the program's place by the check
to show that its limits would catch one.

The weight tree mirrors the program's parameter tree name for name (``embed``, ``head``, ``final_norm``,
``layers[i]`` with ``op_norm``, ``ffn_norm``, ``latent`` (``q``, ``kva``, ``kv_norm``, ``kvb``, ``o``) and
``dense`` or ``moe`` (``router``, ``w1``, ``w3``, ``w2``, ``shared``)), every matrix as (inputs, outputs): a
contract of shapes, stated here and in ``models/lfm2_moe.py::param_shapes``, not an import.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BETA1, ADAM_EPS, INIT_STD = 0.9, 1e-8, 0.02


def seeded_weights(m: Dict[str, Any], seed: int, std: float = INIT_STD, router_gain: float = 2.0) -> Dict[str, Any]:
    """Weights from the seed, numpy float32: normal(0, std), 0.02 at the
    published widths (a rehearsal's narrow layers take a larger one, or their
    outputs vanish beside the residual); norm weights 1 + normal(0, std), so
    that a norm weight applied wrongly shows; the router ``router_gain`` times
    wider, so that its softmax is far from uniform (at 0.04 the logits of a
    normed token of 2,048 channels have deviation 1.8: the largest of 64
    probabilities is ~0.2, not 1/64) and the seventh choice seldom ties the sixth."""
    rng = np.random.default_rng([seed, 0xD5E2])
    h, nh, held = m["hidden_size"], m["num_attention_heads"], m["held_experts"][1] - m["held_experts"][0]
    rank, nope, rope, vd = m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    mat = lambda *shape: (std * rng.standard_normal(shape, dtype=np.float32))
    norm = lambda n: (1.0 + mat(n)).astype(np.float32)
    layers = []
    for i in range(m["num_hidden_layers"]):
        layer: Dict[str, Any] = {"op_norm": norm(h), "ffn_norm": norm(h)}
        layer["latent"] = {"q": mat(h, nh * (nope + rope)), "kva": mat(h, rank + rope), "kv_norm": norm(rank),
                           "kvb": mat(rank, nh * (nope + vd)), "o": mat(nh * vd, h)}
        if i < m["first_k_dense_replace"]:
            f = m["intermediate_size"]
            layer["dense"] = {"w1": mat(h, f), "w3": mat(h, f), "w2": mat(f, h)}
        else:
            f, fs = m["moe_intermediate_size"], m["n_shared_experts"] * m["moe_intermediate_size"]
            layer["moe"] = {"router": router_gain * mat(h, m["n_routed_experts"]), "w1": mat(held, h, f),
                            "w3": mat(held, h, f), "w2": mat(held, f, h),
                            "shared": {"w1": mat(h, fs), "w3": mat(h, fs), "w2": mat(fs, h)}}
        layers.append(layer)
    return {"embed": mat(m["vocab_size"], h), "head": mat(m["vocab_size"], h), "final_norm": norm(h), "layers": layers}


def _rounder(control: Optional[str]):
    if control is None:
        return lambda a: a
    if control != "fp8":
        raise ValueError(f"unknown control {control!r}")
    return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def mscale(factor: float, scale: float) -> float:
    return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim: int, theta: float, s: Dict[str, Any]) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN (float64; the equations of the header)."""
    i = np.arange(dim // 2, dtype=np.float64)
    plain = 1.0 / theta ** (2.0 * i / dim)
    where = lambda beta: dim * math.log(s["original_max_position_embeddings"] / (beta * 2.0 * math.pi)) \
        / (2.0 * math.log(theta))
    low, high = max(math.floor(where(s["beta_fast"])), 0), min(math.ceil(where(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    g = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - g) * plain / s["factor"] + g * plain


def rope(x, m):
    """x (length, heads, rope size): rotate-half rotary embedding at YaRN's frequencies."""
    s, half = m["rope_scaling"], x.shape[-1] // 2
    freq = jnp.asarray(yarn_frequencies(x.shape[-1], m["rope_theta"], s), jnp.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    amplitude = mscale(s["factor"], s["mscale"]) / mscale(s["factor"], s["mscale_all_dim"])
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :] * amplitude
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :] * amplitude
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def softmax_scale(m) -> float:
    s = m["rope_scaling"]
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * mscale(s["factor"], s["mscale_all_dim"]) ** 2


def latent_attention(w, x, m, rd):
    length = x.shape[0]
    nh, rank, nope, vd = m["num_attention_heads"], m["kv_lora_rank"], m["qk_nope_head_dim"], m["v_head_dim"]
    q = (rd(x) @ rd(w["q"])).reshape(length, nh, -1)
    latent = rd(x) @ rd(w["kva"])
    c, k_pe = rms_norm(latent[:, :rank], w["kv_norm"], m["rms_norm_eps"]), latent[:, rank:]
    kv = (rd(c) @ rd(w["kvb"])).reshape(length, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], m)
    k_pe = rope(k_pe[:, None, :], m)  # one head
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (length, nh, k_pe.shape[-1]))], -1)  # explicit per-head keys
    q = jnp.concatenate([q_nope, q_pe], -1)
    causal = jnp.tril(jnp.ones((length, length), bool))
    scale = softmax_scale(m)

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv
        scores = (rd(qh) @ rd(kh).T) * scale
        prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return rd(prob) @ rd(vh)

    out = jax.lax.map(one_head, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))  # a head at a time
    return rd(out.swapaxes(0, 1).reshape(length, nh * vd)) @ rd(w["o"])


def swiglu(x, w1, w3, w2, rd):
    return rd(jax.nn.silu(rd(x) @ rd(w1)) * (rd(x) @ rd(w3))) @ rd(w2)


def routed_ffn(w, x, m, rd):
    """One sequence: (the held experts' part of the sum plus the shared experts, the load of ALL experts,
    the sequence's balance term sum_e f_e P_e)."""
    experts, k = m["n_routed_experts"], m["num_experts_per_tok"]
    prob = jax.nn.softmax(rd(x) @ rd(w["router"]), axis=-1)
    weight, chosen = jax.lax.top_k(prob, k)  # the chosen probabilities as they are
    first, last = m["held_experts"]

    @jax.checkpoint
    def add_expert(out, expert):  # every token through the expert; the 0/1 mask keeps the tokens that chose it
        e, w1, w3, w2 = expert
        mine = (chosen == e).astype(x.dtype)
        return out + (mine * weight).sum(-1, keepdims=True) * swiglu(x, w1, w3, w2, rd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(first, last), w["w1"], w["w3"], w["w2"]))
    out = out + swiglu(x, w["shared"]["w1"], w["shared"]["w3"], w["shared"]["w2"], rd)
    load = (chosen[..., None] == jnp.arange(experts)).sum((0, 1))
    f = jax.lax.stop_gradient(load.astype(jnp.float32)) * experts / (k * x.shape[0])
    return out, load, jnp.sum(f * prob.mean(axis=0))


def layer(m, index: int, rd, w, x):
    """One sequence through layer ``index``: (output, load or None, balance term or None)."""
    h = x + latent_attention(w["latent"], rms_norm(x, w["op_norm"], m["rms_norm_eps"]), m, rd)
    normed = rms_norm(h, w["ffn_norm"], m["rms_norm_eps"])
    if index < m["first_k_dense_replace"]:
        return h + swiglu(normed, w["dense"]["w1"], w["dense"]["w3"], w["dense"]["w2"], rd), None, None
    out, load, balance = routed_ffn(w["moe"], normed, m, rd)
    return h + out, load, balance


def forward(m, weights, tokens, control: Optional[str] = None):
    """One sequence: (logits (length, held vocabulary), load (routed layers, experts), the routed layers'
    balance terms added up)."""
    rd = _rounder(control)
    x = weights["embed"][tokens]
    loads, balance = [], 0.0
    for i, w in enumerate(weights["layers"]):
        x, load, term = jax.checkpoint(functools.partial(layer, m, i, rd))(w, x)
        if load is not None:
            loads.append(load)
            balance = balance + term
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    return rd(x) @ rd(weights["head"]).T, jnp.stack(loads), balance


def token_loss(logits, targets):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]


@functools.lru_cache(maxsize=None)
def _compiled(model_key: str, control: Optional[str]):
    m = json.loads(model_key)

    def sequence_loss(weights, alpha, x, y):
        logits, load, balance = forward(m, weights, x, control)
        loss = token_loss(logits, y)
        return loss.mean() + alpha * balance, (load, loss, balance)

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


def eval_token_loss(m, weights, x: np.ndarray, y: np.ndarray, control: Optional[str] = None) -> np.ndarray:
    """Cross-entropy per token (sequences, length) of held-out sequences (no balance term: a validation loss)."""
    with jax.default_matmul_precision("highest"):
        grad = _programs(m, control)[0]  # the one compiled program; its gradients are not looked at here
        weights = jax.tree_util.tree_map(jnp.asarray, weights)
        return np.stack([np.asarray(grad(weights, 0.0, jnp.asarray(xs), jnp.asarray(ys))[0][1][1])
                         for xs, ys in zip(x, y)])


def train(m, weights, batches: Sequence[Tuple[np.ndarray, np.ndarray]], genes: Dict[str, float],
          control: Optional[str] = None) -> Dict[str, Any]:
    """AdamW steps from ``weights`` over ``batches`` (each (x, y) of whole
    sequences), the first step numbered 0.  Returns the weights (on the
    device), AdamW's first moment after the last step (on the host), each
    step's loss (balance term included), its balance term alone (before
    ``aux_alpha``) and its load (routed layers, experts).  ``m["train_steps"]``
    sets the warm-up's length."""
    with jax.default_matmul_precision("highest"):
        grad, add = _programs(m, control)
        weights = jax.tree_util.tree_map(jnp.asarray, weights)
        paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(weights)[0]]
        moments: Optional[List[Tuple[np.ndarray, np.ndarray]]] = None
        losses, loads, balances = [], [], []
        for step, (xb, yb) in enumerate(batches):
            total = load = None
            loss = balance = 0.0
            for xs, ys in zip(xb, yb):  # a sequence at a time, gradients added up
                (value, (seq_load, _, seq_balance)), g = grad(weights, genes["aux_alpha"], jnp.asarray(xs),
                                                              jnp.asarray(ys))
                total = g if total is None else add(total, g)
                load = seq_load if load is None else load + seq_load
                loss += float(value) / len(xb)
                balance += float(seq_balance) / len(xb)
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
            losses.append(loss)
            balances.append(balance)
            loads.append(np.asarray(load))
        moment = jax.tree_util.tree_unflatten(tree, [mom for mom, _ in moments])
        return {"weights": weights, "moment": moment, "losses": losses, "balances": balances, "loads": loads}
