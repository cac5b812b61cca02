"""Plain reference of the ``qwen3_next`` family: one expert-parallel rank's share of
Qwen3-Next-80B-A3B-Instruct (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json,
``model_type`` ``qwen3_next``) in straightforward ``jax.numpy``, float32, every
product under ``jax.default_matmul_precision("highest")``.  Imports nothing of
``gentun_tpu`` and takes nothing it has made.

Layer ``l`` of type ``t = layer_types[l]``, one sequence ``x`` (length, hidden); ``m`` is the configuration's model
block (``family.model_block``: the published keys under their published names)::

    h = x + Mix_t(RMSNorm(x));   y = h + FFN(RMSNorm(h))           eps rms_norm_eps, a weight per channel, no bias

    t = linear_attention (Gated DeltaNet): linear_num_key_heads key heads and linear_num_value_heads value heads of
        linear_key_head_dim / linear_value_head_dim; value head h belongs to key head h // (value heads / key heads)
        [q ; k ; v ; z] = x W_qkvz       hidden -> keys + keys + values + values columns, four blocks in this order
        [b ; a]        = x W_ba          hidden -> one b and one a a value head, two blocks in this order
        [q ; k ; v]    = silu(conv([q ; k ; v]))    causal, depthwise, linear_conv_kernel_dim taps, no bias:
                         out[t, c] = sum_j kernel[c, j] in[t - (taps - 1) + j, c], zeros before position 0
        beta_t = sigmoid(b_t);   g_t = -exp(A_log) * softplus(a_t + dt_bias)       a value head
        q = l2norm(q) / sqrt(key size),  k = l2norm(k)       x / sqrt(sum x^2 + 1e-6), a key head
        a value head, S_0 = 0 (key size x value size), ONE POSITION AT A TIME:
            S'  = exp(g_t) S_{t-1}
            S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
            o_t = S_t^T q_t
        o_t = RMSNorm(o_t; w_n) * silu(z_t)    a value head, over its value size; one w_n for all heads
        Mix = concat_heads(o) W_out
    t = full_attention (gated): num_attention_heads heads, num_key_value_heads key-value heads, head_dim
        [q ; gate] = x W_q -> a head's columns are [its query | its gate], head_dim each;  k = x W_k;  v = x W_v
        q = RMSNorm(q; w_q), k = RMSNorm(k; w_k) over head_dim, a head
        rope on the first partial_rotary_factor * head_dim columns of q and k: rotate-half inside them, pairs
        (c, c + rotary / 2), inv_freq_c = rope_theta^(-2c / rotary); the other columns pass
        o = softmax over the keys j <= i of (q_i . k_j / sqrt(head_dim)) v;  query head n uses key-value head
        n // (heads / kv heads);  Mix = (o * sigmoid(gate)) W_o
    FFN:  p = softmax(x W_r) over ALL num_experts, float32;  chosen = the num_experts_per_tok largest;
          w_e = p_e / sum over the chosen of p   (norm_topk_prob true)
          out = sum over the HELD experts e of [e chosen] w_e (silu(x W1_e) * x W3_e) W2_e
                -- a loop over the held experts with a 0/1 mask; no sort, no grouped product; what the absent
                experts would add is left out --
              + sigmoid(x . w_g) * (silu(x W1_s) * x W3_s) W2_s        the shared expert, whole on every rank
    output:  RMSNorm, logits = x H' over the held rows H of the untied head; next-token cross-entropy
    loss  =  mean cross-entropy + alpha * sum over layers of  mean over sequences of  sum_e f_e P_e  over ALL experts:
             f_e = num_experts / (k L) * #(tokens of the sequence that chose e), a count without a gradient;
             P_e = the sequence's mean of p_e.  The term is the *recipe's* (its weight alpha is the fifth gene,
             ``aux_alpha``): the published config gives no balance coefficient

Departures from the published model, each noted in the configuration's ``assumed``: norms are ``x_hat * w`` with ``w``
from 1 where the published ones are ``x_hat * (1 + w)`` with ``w`` from 0 (the same function of the same updates
without weight decay on them; ``m["zero_centred_norms"]`` runs the published form, for the test that says so); the
columns of ``W_qkvz`` and ``W_ba`` are blocks, where the published weights interleave them by key head (a permutation
of columns of seeded weights); the multi-token-prediction head is not built; the balance term is the recipe's.

Training: mean loss over the batch's tokens plus the balance term over the batch's sequences, gradients by
``jax.grad``, AdamW written out (beta1 0.9, eps 1e-8, decoupled decay on everything but the norm weights, ``A_log``
and ``dt_bias``, bias-corrected moments, linear warm-up over ``warmup_frac * train_steps`` steps then constant).

Departures from "one array at a time", all of them only so that the published widths fit a 16 GB chip beside the
window's loaded programs, none of them a change of arithmetic: a batch is taken a sequence at a time and the
gradients added up; each layer, each held expert, each (head, block of queries) of attention and each block of
``RECURRENCE_BLOCK`` positions of the recurrence is under ``jax.checkpoint``, so its interior is computed again in the
backward pass and not kept (the recurrence's gradient would otherwise keep a state a head and position: 34 GB a layer
at 16,384 positions); the loops are ``lax.scan`` / ``lax.map``; AdamW's two moments live on the host between steps
and the update runs leaf by leaf.  The caller frees the program's state first.

``control="fp8"`` rounds both inputs of every matrix product to float8 e4m3 (the nearest precision below the
configuration's bfloat16): the reference itself in a lower precision, put in the program's place by the check to show
that its limits would catch one.  The recurrence's own products stay float32 there; its inputs q, k, v come from
rounded products.

The weight tree mirrors the program's parameter tree name for name (``embed``, ``head``, ``final_norm``, ``layers[i]``
with ``op_norm``, ``ffn_norm``, ``delta`` (``qkvz``, ``ba``, ``kernel``, ``A_log``, ``dt_bias``, ``norm``, ``out``) or
``attn`` (``q``, ``k``, ``v``, ``o``, ``q_norm``, ``k_norm``), and ``moe`` (``router``, ``w1``, ``w3``, ``w2``,
``shared`` (``w1``, ``w3``, ``w2``), ``shared_gate``)), every matrix as (inputs, outputs): a contract of shapes, stated
here and in ``models/lfm2_moe.py::param_shapes``, not an import.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BETA1, ADAM_EPS, INIT_STD, L2_EPS = 0.9, 1e-8, 0.02, 1e-6
#: Queries whose scores against every key are alive at once, a head: 1,024 x 16,384 float32 = 67 MB.
QUERY_BLOCK = 1024
#: Positions of the recurrence whose states the backward pass holds at once: 128 x 32 heads x 128 x 128 float32 = 268 MB.
RECURRENCE_BLOCK = 128
#: Leaves that are no matrix and take no weight decay.
UNDECAYED = ("norm", "A_log", "dt_bias")


def widths(m: Dict[str, Any]) -> Tuple[int, int]:
    """(the columns of a linear-attention layer's q, and of its k; those of its v, and of its z)."""
    return m["linear_num_key_heads"] * m["linear_key_head_dim"], m["linear_num_value_heads"] * m["linear_value_head_dim"]


def seeded_weights(m: Dict[str, Any], seed: int, std: float = INIT_STD, router_gain: float = 2.0,
                   embed_std: Optional[float] = None, out_std: Optional[float] = None,
                   conv_std: Optional[float] = None) -> Dict[str, Any]:
    """Weights from the seed, numpy float32: normal(0, std), 0.02 at the
    published widths (a rehearsal's narrow layers take a larger one); norm
    weights and ``dt_bias`` 1 + normal(0, std), so that one applied wrongly
    shows; ``A_log`` the log of a rate uniform on (0.05, 16): decays from almost
    none to a state forgotten within a position; the router ``router_gain``
    times wider, so that its softmax is far from uniform.  ``embed_std``,
    ``out_std`` (the matrices that write into the residual stream: both mixers'
    output products, the experts' and the shared expert's W2) and ``conv_std``
    (the convolutions' kernels: four taps of 0.02 would shrink v fifty-fold) are
    each ``std`` unless given."""
    rng = np.random.default_rng([seed, 0x3E11])
    h, hd, held = m["hidden_size"], m["head_dim"], m["held_experts"][1] - m["held_experts"][0]
    nh, nkv, f, fs = m["num_attention_heads"], m["num_key_value_heads"], m["moe_intermediate_size"], \
        m["shared_expert_intermediate_size"]
    keys, values = widths(m)
    nv = m["linear_num_value_heads"]
    embed_std, out_std, conv_std = (std if given is None else given for given in (embed_std, out_std, conv_std))
    mat = lambda *shape, std=std: (std * rng.standard_normal(shape, dtype=np.float32))
    norm = lambda n: (1.0 + mat(n)).astype(np.float32)
    layers = []
    for kind in m["layer_types"]:
        layer = {"op_norm": norm(h), "ffn_norm": norm(h)}
        if kind == "linear_attention":
            layer["delta"] = {"qkvz": mat(h, 2 * keys + 2 * values), "ba": mat(h, 2 * nv),
                              "kernel": mat(2 * keys + values, m["linear_conv_kernel_dim"], std=conv_std),
                              "A_log": np.log(rng.uniform(0.05, 16.0, nv)).astype(np.float32), "dt_bias": norm(nv),
                              "norm": norm(m["linear_value_head_dim"]), "out": mat(values, h, std=out_std)}
        else:
            layer["attn"] = {"q": mat(h, 2 * nh * hd), "k": mat(h, nkv * hd), "v": mat(h, nkv * hd),
                             "o": mat(nh * hd, h, std=out_std), "q_norm": norm(hd), "k_norm": norm(hd)}
        layer["moe"] = {"router": router_gain * mat(h, m["num_experts"]), "w1": mat(held, h, f), "w3": mat(held, h, f),
                        "w2": mat(held, f, h, std=out_std),
                        "shared": {"w1": mat(h, fs), "w3": mat(h, fs), "w2": mat(fs, h, std=out_std)},
                        "shared_gate": mat(h)}
        layers.append(layer)
    return {"embed": mat(m["vocab_size"], h, std=embed_std), "head": mat(m["vocab_size"], h), "final_norm": norm(h),
            "layers": layers}


def _rounder(control: Optional[str]):
    if control is None:
        return lambda a: a
    if control != "fp8":
        raise ValueError(f"unknown control {control!r}")
    return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms_norm(x, weight, eps, zero_centred: bool = False):
    """``x_hat * w``; the published form ``x_hat * (1 + w)`` with ``zero_centred`` (the norms of the stream and of q, k)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + weight if zero_centred else weight)


def partial_rope(x, m):
    """x (length, heads, head size): rotate-half on the leading ``partial_rotary_factor * head_dim`` columns."""
    rotary = int(m["head_dim"] * m["partial_rotary_factor"])
    half = rotary // 2
    freq = 1.0 / float(m["rope_theta"]) ** (2.0 * np.arange(half, dtype=np.float64) / rotary)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, passing = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, passing], axis=-1)


def attention(w, x, m, rd):
    """One sequence (length, hidden) through the gated full attention."""
    length = x.shape[0]
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    centred = bool(m.get("zero_centred_norms"))
    q_gate = (rd(x) @ rd(w["q"])).reshape(length, nh, 2 * hd)
    q, gate = q_gate[..., :hd], q_gate[..., hd:]
    k = (rd(x) @ rd(w["k"])).reshape(length, nkv, hd)
    v = (rd(x) @ rd(w["v"])).reshape(length, nkv, hd)
    q = partial_rope(rms_norm(q, w["q_norm"], m["rms_norm_eps"], centred), m)
    k = partial_rope(rms_norm(k, w["k_norm"], m["rms_norm_eps"], centred), m)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)  # head n <- key-value head n // (nh / nkv)
    block = min(QUERY_BLOCK, length)
    assert length % block == 0, (length, block)
    positions = jnp.arange(length)

    def one_head(qkv):
        qh, kh, vh = qkv

        @jax.checkpoint
        def one_block(args):
            qb, ib = args
            mask = (ib[:, None] >= positions[None, :]).astype(jnp.int32)  # (block, length) of 0 and 1
            scores = (rd(qb) @ rd(kh).T) / math.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(mask == 1, scores, -jnp.inf), axis=-1)
            return rd(prob) @ rd(vh)

        return jax.lax.map(one_block, (qh.reshape(-1, block, hd), positions.reshape(-1, block))).reshape(length, hd)

    out = jax.lax.map(one_head, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))  # a head at a time
    out = out.swapaxes(0, 1) * jax.nn.sigmoid(gate)
    return rd(out.reshape(length, nh * hd)) @ rd(w["o"])


def delta_rule(q, k, v, g, beta):
    """The gated delta rule one position at a time: q, k (length, heads, key size), v (length, heads, value size),
    g, beta (length, heads), every value head with its own q and k.  Returns o (length, heads, value size)."""
    length, heads, dk = q.shape

    def one_position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hde,hd->he", state, k_t)
        state = state + jnp.einsum("hd,he->hde", k_t, beta_t[:, None] * (v_t - read))
        return state, jnp.einsum("hde,hd->he", state, q_t)

    @jax.checkpoint
    def one_block(state, block):
        return jax.lax.scan(one_position, state, block)

    pad = -length % min(RECURRENCE_BLOCK, length)
    block = min(RECURRENCE_BLOCK, length)
    # positions past the end write nothing (beta 0) and are dropped
    blocks = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape((-1, block) + a.shape[1:])
                   for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(one_block, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), blocks)
    return out.reshape((-1,) + out.shape[2:])[:length]


def causal_conv_silu(x, kernel):
    """x (length, channels), kernel (channels, taps): out[t] = sum_j kernel[:, j] x[t - (taps - 1) + j], then SiLU."""
    taps = kernel.shape[1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(kernel[:, j] * padded[j:j + x.shape[0]] for j in range(taps)))


def linear_attention(w, x, m, rd):
    """One sequence (length, hidden) through the Gated DeltaNet mixer."""
    length = x.shape[0]
    nk, nv, dk, dv = (m["linear_num_key_heads"], m["linear_num_value_heads"], m["linear_key_head_dim"],
                      m["linear_value_head_dim"])
    keys, values = widths(m)
    qkvz = rd(x) @ rd(w["qkvz"])
    ba = rd(x) @ rd(w["ba"])
    mixed = causal_conv_silu(qkvz[:, :2 * keys + values], w["kernel"])
    q, k = (mixed[:, lo:lo + keys].reshape(length, nk, dk) for lo in (0, keys))
    v, z = mixed[:, 2 * keys:].reshape(length, nv, dv), qkvz[:, 2 * keys + values:].reshape(length, nv, dv)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, nv:] + w["dt_bias"])
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    q, k = unit(q) / math.sqrt(dk), unit(k)
    q, k = jnp.repeat(q, nv // nk, axis=1), jnp.repeat(k, nv // nk, axis=1)  # value head h <- key head h // (nv / nk)
    out = delta_rule(q, k, v, g, beta)
    out = rms_norm(out, w["norm"], m["rms_norm_eps"]) * jax.nn.silu(z)
    return rd(out.reshape(length, values)) @ rd(w["out"])


def swiglu(x, w1, w3, w2, rd):
    return rd(jax.nn.silu(rd(x) @ rd(w1)) * (rd(x) @ rd(w3))) @ rd(w2)


def routed_ffn(w, x, m, rd):
    """One sequence: (the held experts' part of the sum plus the gated shared expert, the load of ALL experts, the
    sequence's balance term sum_e f_e P_e)."""
    experts, k = m["num_experts"], m["num_experts_per_tok"]
    prob = jax.nn.softmax(rd(x) @ rd(w["router"]), axis=-1)
    picked, chosen = jax.lax.top_k(prob, k)
    weight = picked / picked.sum(-1, keepdims=True)  # norm_topk_prob: over the chosen, held here or not
    first, last = m["held_experts"]

    @jax.checkpoint
    def add_expert(out, expert):  # every token through the expert; the 0/1 mask keeps the tokens that chose it
        e, w1, w3, w2 = expert
        mine = (chosen == e).astype(x.dtype)
        return out + (mine * weight).sum(-1, keepdims=True) * swiglu(x, w1, w3, w2, rd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(first, last), w["w1"], w["w3"], w["w2"]))
    if m.get("shared_expert", True):  # False: the routed part alone (what the shares-add-up test takes apart)
        shared = w["shared"]
        out = out + jax.nn.sigmoid(x @ w["shared_gate"])[:, None] * swiglu(x, shared["w1"], shared["w3"], shared["w2"], rd)
    load = (chosen[..., None] == jnp.arange(experts)).sum((0, 1))
    f = jax.lax.stop_gradient(load.astype(jnp.float32)) * experts / (k * x.shape[0])
    return out, load, jnp.sum(f * prob.mean(axis=0))


def layer(m, index: int, rd, w, x):
    """One sequence through layer ``index`` (of the layers kept): (output, load, balance term)."""
    kind, eps, centred = m["layer_types"][index], m["rms_norm_eps"], bool(m.get("zero_centred_norms"))
    normed = rms_norm(x, w["op_norm"], eps, centred)
    if kind == "linear_attention":
        h = x + linear_attention(w["delta"], normed, m, rd)
    else:
        assert kind == "full_attention", kind
        h = x + attention(w["attn"], normed, m, rd)
    out, load, balance = routed_ffn(w["moe"], rms_norm(h, w["ffn_norm"], eps, centred), m, rd)
    return h + out, load, balance


def forward(m, weights, tokens, control: Optional[str] = None):
    """One sequence: (logits (length, held vocabulary), load (layers, experts), the layers' balance terms added up)."""
    rd = _rounder(control)
    x = weights["embed"][tokens]
    loads, balance = [], 0.0
    for i, w in enumerate(weights["layers"]):
        x, load, term = jax.checkpoint(functools.partial(layer, m, i, rd))(w, x)
        loads.append(load)
        balance = balance + term
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"], bool(m.get("zero_centred_norms")))
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
    ``aux_alpha``) and its load (layers, experts).  ``m["train_steps"]``
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
                                          decay=not any(name in str(path[-1]) for name in UNDECAYED))
                leaves[i], grads[i] = p, None
                moments[i] = (np.asarray(mom), np.asarray(var))
            weights = jax.tree_util.tree_unflatten(tree, leaves)
            losses.append(loss)
            balances.append(balance)
            loads.append(np.asarray(load))
        moment = jax.tree_util.tree_unflatten(tree, [mom for mom, _ in moments])
        return {"weights": weights, "moment": moment, "losses": losses, "balances": balances, "loads": loads}
