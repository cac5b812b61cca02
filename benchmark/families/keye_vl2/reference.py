"""Plain reference of the ``keye_vl2`` family: one expert-parallel rank's share of the language model of
Keye-VL-2.0-30B-A3B (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json, ``model_type``
``KeyeVL2``) on text, in straightforward ``jax.numpy``, float32, every product under
``jax.default_matmul_precision("highest")``.  Imports nothing of ``gentun_tpu`` and takes nothing it has made.

Every layer is alike.  Input ``x`` (tokens, hidden), ``u = RMSNorm(x)``, ``T`` the length, ``k = sa_config.topk``;
``m`` is the configuration's model block (``family.model_block``: the published keys under their published names,
``sa_config``'s sizes flat)::

    h = x + Attn(u);   y = h + MoE(RMSNorm(h))                      eps rms_norm_eps, a weight per channel, no bias
    trunk:     q = u W_q -> num_attention_heads x head_dim;  k, v = u W_k, u W_v -> num_key_value_heads x head_dim;
               RMSNorm over head_dim on every head of q and of k (weights q_norm, k_norm: Qwen3's convention);
               rope, rotate-half pairs (c, c + head_dim/2), pair c at the angle  pos_s(c)[t] * theta^(-2c/head_dim)
               where s(c) is the position stream of pair c: mrope_section [16, 24, 24] gives pairs 0-15 the temporal
               stream, 16-39 and 40-63 the two spatial ones (on text every stream is the token's index);
               query head n uses key-value head n // (heads / kv heads);  scale head_dim^-0.5
    indexer:   u is DETACHED here (no gradient passes into the residual stream);
               qI_j = rope(u W_qI[j]) (j = 1..indexer_num_heads, indexer_head_dim wide),  kI = rope(u W_kI) (ONE key
               head, shared by every j),  w = u W_w (one number a head and token);  rope on every column of qI and kI
               at the temporal stream's positions, the same theta;
               I[t, s] = sum_j (w[t, j] * heads^-0.5 * size^-0.5) * relu(qI_j[t] . kI[s])       for s <= t
    selection: tau[t] = the k-th largest of I[t, 0..t] (``lax.top_k`` over the row with minus infinity ahead of t; so
               minus infinity where t has fewer than k keys);  S_t = {s <= t : I[t, s] >= tau[t]}  -- an explicit 0/1
               array (block of queries x T), built a block of queries at a time; ties at tau are kept on both sides
    core:      each head is softmax attention over S_t alone: softmax_s (q_t . k_s * scale) for s in S_t;  out W_o
    L_I:       p[t, s] = (1 / heads) sum_h prob_h[t, s] on S_t, a constant (``stop_gradient``);
               L_I = mean_t sum_{s in S_t} p[t, s] log(p[t, s] / softmax_{S_t}(I[t, :])[s]),  one a layer
    MoE:       p = softmax(x W_r) over ALL num_experts, float32;  chosen = the num_experts_per_tok largest;
               w_e = p_e / sum over the chosen of p   (norm_topk_prob true)
               out = sum over the HELD experts e of [e chosen] w_e (silu(x W1_e) * x W3_e) W2_e
                   -- a loop over the held experts with a 0/1 mask; what the absent experts would add is left out --
    output:    RMSNorm, logits = x H' over the held rows H of the untied head; next-token cross-entropy
    loss    =  mean cross-entropy + alpha * sum over layers of the sequence's balance term sum_e f_e P_e (Mellum2's:
               f_e = num_experts / (k_r L) * #(tokens that chose e), a count; P_e the mean of p_e; alpha the recipe's
               fifth gene ``aux_alpha``) + sum over layers of L_I (weight 1: W_qI, W_kI, W_w get their gradient from
               L_I alone, and nothing else gets any from it)

Departures from the published model, each under the configuration's ``assumed`` with its other reading: the indexer
reads ``u`` (DeepSeek-V3.2 reads a query latent this model lacks); rope on all its columns; no norm on ``kI``; the two
chunk sizes of ``sa_config`` are a tiling and set no semantics; the sparse training stage alone (no dense warm-up);
the vision tower and image positions are not built.

Training: the loss above a sequence at a time, gradients by ``jax.grad`` and added up, AdamW written out (beta1
0.9, eps 1e-8, decoupled decay on everything but the norm weights, bias-corrected moments, linear warm-up over
``warmup_frac * train_steps`` steps then constant).

Departures from "one array at a time", only so that the published widths fit a 16 GB chip, none a change of
arithmetic: each layer, each held expert and each block of queries is under ``jax.checkpoint``; the loops over held
experts, query blocks and heads are ``lax.scan`` / ``lax.map``; the heads' mean share ``p`` is made by a pass of its
own over the heads (from detached q and k: it is a constant) so that no head's scores outlive its turn; AdamW's
moments live on the host between steps and the update runs leaf by leaf.

``control="fp8"`` rounds both inputs of every product to float8 e4m3 (the nearest precision below the
configuration's bfloat16), the indexer's products among them.

The weight tree mirrors the program's (``embed``, ``head``, ``final_norm``, ``layers[i]`` with ``op_norm``,
``ffn_norm``, ``attn`` (``q``, ``k``, ``v``, ``o``, ``q_norm``, ``k_norm``), ``indexer`` (``q``, ``k``, ``w``) and
``moe`` (``router``, ``w1``, ``w3``, ``w2``)), every matrix as (inputs, outputs): a contract of shapes, stated here and
in ``models/lfm2_moe.py::param_shapes``, not an import.
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
#: Queries whose scores against every key are alive at once, a head: 1,024 x 16,384 float32 = 67 MB.
QUERY_BLOCK = 1024


def seeded_weights(m: Dict[str, Any], seed: int, std: float = INIT_STD, router_gain: float = 2.0,
                   embed_std: Optional[float] = None, out_std: Optional[float] = None) -> Dict[str, Any]:
    """Weights from the seed, numpy float32: normal(0, std); norm weights (a layer's two, q's and k's, the final
    one) 1 + normal(0, std), so that a norm weight applied wrongly shows; the router ``router_gain`` times wider;
    ``embed_std`` and ``out_std`` (each ``std`` unless given) the embedding's and the deviation of the two
    matrices that write into the residual stream, attention's W_o and the experts' W2 (the configuration's
    ``check`` says what it takes and why)."""
    rng = np.random.default_rng([seed, 0x6EBE])
    h, hd, held = m["hidden_size"], m["head_dim"], m["held_experts"][1] - m["held_experts"][0]
    nh, nkv, f = m["num_attention_heads"], m["num_key_value_heads"], m["moe_intermediate_size"]
    ni, di = m["indexer_num_heads"], m["indexer_head_dim"]
    embed_std, out_std = std if embed_std is None else embed_std, std if out_std is None else out_std
    mat = lambda *shape, std=std: (std * rng.standard_normal(shape, dtype=np.float32))
    norm = lambda n: (1.0 + mat(n)).astype(np.float32)
    layers = [{"op_norm": norm(h), "ffn_norm": norm(h),
               "attn": {"q": mat(h, nh * hd), "k": mat(h, nkv * hd), "v": mat(h, nkv * hd),
                        "o": mat(nh * hd, h, std=out_std), "q_norm": norm(hd), "k_norm": norm(hd)},
               "indexer": {"q": mat(h, ni * di), "k": mat(h, di), "w": mat(h, ni)},
               "moe": {"router": router_gain * mat(h, m["num_experts"]), "w1": mat(held, h, f), "w3": mat(held, h, f),
                       "w2": mat(held, f, h, std=out_std)}}
              for _ in range(m["num_hidden_layers"])]
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


def text_positions(length: int, streams: int = 3):
    """The position streams of a text: the token's index in every one, (streams, length)."""
    return jnp.broadcast_to(jnp.arange(length, dtype=jnp.float32), (streams, length))


def rope(x, theta: float, positions, sections: Optional[Sequence[int]] = None):
    """x (length, heads, head size): rotate-half rotary embedding, pair ``c`` at the angle
    ``positions[s(c)][t] * theta^(-2c/size)``; ``sections`` shares the pairs out over the streams in order
    (None: every pair reads the first stream)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (2.0 * np.arange(half, dtype=np.float64) / x.shape[-1])
    stream_of = np.zeros(half, np.int64) if sections is None else np.repeat(np.arange(len(sections)), sections)
    assert len(stream_of) == half, (sections, half)
    angle = jnp.asarray(positions, jnp.float32)[stream_of].T * jnp.asarray(freq, jnp.float32)[None, :]  # (length, half)
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def indexer_operands(wi, u, m, rd, positions):
    """(qI (length, heads, size), kI (length, size), w (length, heads) with the score's two scales on it) from the
    DETACHED normed input ``u``."""
    length = u.shape[0]
    ni, di = m["indexer_num_heads"], m["indexer_head_dim"]
    u = jax.lax.stop_gradient(u)
    qi = rope((rd(u) @ rd(wi["q"])).reshape(length, ni, di), m["rope_theta"], positions)
    ki = rope((rd(u) @ rd(wi["k"])).reshape(length, 1, di), m["rope_theta"], positions)[:, 0]
    return qi, ki, (rd(u) @ rd(wi["w"])) * (ni ** -0.5 * di ** -0.5)


def indexer_scores(qi, ki, w, rd):
    """I (queries, keys): ``sum_j w[t, j] relu(qI_j[t] . kI[s])``."""
    return jnp.sum(w[:, :, None] * jax.nn.relu(jnp.einsum("qjd,kd->qjk", rd(qi), rd(ki))), axis=1)


def chosen(index, at, topk: int):
    """The explicit 0/1 array (queries, keys) of the keys each query keeps: ``s <= t`` and ``I[t, s] >= tau[t]``,
    ``tau`` the ``topk``-th largest of the row's scores up to ``t`` (``lax.top_k``; minus infinity ahead of ``t``)."""
    causal = at[:, None] >= jnp.arange(index.shape[1])[None, :]
    tau = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), min(topk, index.shape[1]))[0][:, -1]
    return (causal & (index >= tau[:, None])).astype(jnp.int32)


def attention(w, wi, u, m, rd, positions=None):
    """One sequence (length, hidden) of normed inputs through the attention: (output (length, hidden), L_I, the
    (query, key) pairs kept)."""
    length = u.shape[0]
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    theta, sections = m["rope_theta"], m["mrope_section"]
    positions = text_positions(length, len(sections)) if positions is None else positions
    q = (rd(u) @ rd(w["q"])).reshape(length, nh, hd)
    k = (rd(u) @ rd(w["k"])).reshape(length, nkv, hd)
    v = (rd(u) @ rd(w["v"])).reshape(length, nkv, hd)
    if m.get("qk_norm", True):
        q, k = rms_norm(q, w["q_norm"], m["rms_norm_eps"]), rms_norm(k, w["k_norm"], m["rms_norm_eps"])
    q, k = rope(q, theta, positions, sections), rope(k, theta, positions, sections)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)  # head n <- key-value head n // (nh / nkv)
    kh, vh = k.swapaxes(0, 1), v.swapaxes(0, 1)  # (heads, length, size)
    qi, ki, wt = indexer_operands(wi, u, m, rd, positions)
    block = min(QUERY_BLOCK, length)
    assert length % block == 0, (length, block)

    @jax.checkpoint
    def one_block(args):
        qb, qib, wb, at = args  # (block, heads, size), (block, indexer heads, size), (block, indexer heads), (block,)
        index = indexer_scores(qib, ki, wb, rd)
        mask = chosen(index, at, m["topk"])  # (block, length) of 0 and 1

        def head_prob(head):
            qh, k_h = head
            scores = (rd(qh) @ rd(k_h).T) / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(mask == 1, scores, -jnp.inf), axis=-1)

        detached = jax.lax.stop_gradient((qb.swapaxes(0, 1), kh))
        share = jax.lax.stop_gradient(
            jax.lax.scan(lambda total, head: (total + head_prob(head), None), jnp.zeros(mask.shape, jnp.float32),
                         detached)[0] / nh)
        out = jax.lax.map(jax.checkpoint(lambda head: rd(head_prob(head[:2])) @ rd(head[2])), (qb.swapaxes(0, 1), kh, vh))
        log_index = jax.nn.log_softmax(jnp.where(mask == 1, index, -jnp.inf), axis=-1)
        terms = jax.scipy.special.xlogy(share, share) - share * jnp.where(mask == 1, log_index, 0.0)
        return out.swapaxes(0, 1), jnp.sum(jnp.where(mask == 1, terms, 0.0)), jnp.sum(mask)

    rows = lambda a: a.reshape(-1, block, *a.shape[1:])
    out, loss, pairs = jax.lax.map(one_block, (rows(q), rows(qi), rows(wt), rows(jnp.arange(length))))
    return rd(out.reshape(length, nh * hd)) @ rd(w["o"]), jnp.sum(loss) / length, jnp.sum(pairs)


def chosen_keys(wi, u, m, rd=lambda a: a, positions=None) -> jnp.ndarray:
    """The whole 0/1 array (length, length), as bool, of one layer's selection from its normed input ``u``, a block
    of queries at a time (the tests' and the comparison's: which keys each query kept)."""
    length = u.shape[0]
    positions = text_positions(length, len(m["mrope_section"])) if positions is None else positions
    qi, ki, wt = indexer_operands(wi, u, m, rd, positions)
    block = min(QUERY_BLOCK, length)
    rows = lambda a: a.reshape(-1, block, *a.shape[1:])
    one_block = lambda args: chosen(indexer_scores(args[0], ki, args[1], rd), args[2], m["topk"]) == 1
    return jax.lax.map(one_block, (rows(qi), rows(wt), rows(jnp.arange(length)))).reshape(length, length)


def selections(m, weights, tokens, control: Optional[str] = None) -> np.ndarray:
    """Which keys each query of each layer keeps on one sequence ``tokens``: bool (layers, length, length), on the
    host."""
    rd = _rounder(control)
    pick = jax.jit(lambda w, x: chosen_keys(w["indexer"], rms_norm(x, w["op_norm"], m["rms_norm_eps"]), m, rd))
    step = jax.jit(lambda w, x: layer(m, 0, rd, w, x)[0])
    with jax.default_matmul_precision("highest"):
        x, masks = jnp.asarray(weights["embed"])[jnp.asarray(tokens)], []
        for w in weights["layers"]:
            w = jax.tree_util.tree_map(jnp.asarray, w)
            masks.append(np.asarray(pick(w, x)))
            x = step(w, x)
    return np.stack(masks)


def swiglu(x, w1, w3, w2, rd):
    return rd(jax.nn.silu(rd(x) @ rd(w1)) * (rd(x) @ rd(w3))) @ rd(w2)


def routed_ffn(w, x, m, rd):
    """One sequence: (the held experts' part of the sum, the load of ALL experts, the sequence's balance term
    sum_e f_e P_e)."""
    experts, k = m["num_experts"], m["num_experts_per_tok"]
    prob = jax.nn.softmax(rd(x) @ rd(w["router"]), axis=-1)
    picked, choice = jax.lax.top_k(prob, k)
    weight = picked / picked.sum(-1, keepdims=True)  # norm_topk_prob: over the chosen, held here or not
    first, last = m["held_experts"]

    @jax.checkpoint
    def add_expert(out, expert):  # every token through the expert; the 0/1 mask keeps the tokens that chose it
        e, w1, w3, w2 = expert
        mine = (choice == e).astype(x.dtype)
        return out + (mine * weight).sum(-1, keepdims=True) * swiglu(x, w1, w3, w2, rd), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(first, last), w["w1"], w["w3"], w["w2"]))
    load = (choice[..., None] == jnp.arange(experts)).sum((0, 1))
    f = jax.lax.stop_gradient(load.astype(jnp.float32)) * experts / (k * x.shape[0])
    return out, load, jnp.sum(f * prob.mean(axis=0))


def layer(m, index: int, rd, w, x, positions=None):
    """One sequence through layer ``index`` (of the layers kept): (output, load, balance term, (L_I, pairs kept))."""
    mixed, loss, pairs = attention(w["attn"], w["indexer"], rms_norm(x, w["op_norm"], m["rms_norm_eps"]), m, rd, positions)
    h = x + mixed
    out, load, balance = routed_ffn(w["moe"], rms_norm(h, w["ffn_norm"], m["rms_norm_eps"]), m, rd)
    return h + out, load, balance, (loss, pairs)


def forward(m, weights, tokens, control: Optional[str] = None):
    """One sequence: (logits (length, held vocabulary), load (layers, experts), the layers' balance terms added up,
    (the layers' L_I added up, the pairs each layer kept (layers,)))."""
    rd = _rounder(control)
    x = weights["embed"][tokens]
    loads, pairs, balance, indexer = [], [], 0.0, 0.0
    for i, w in enumerate(weights["layers"]):
        x, load, term, (loss, kept) = jax.checkpoint(functools.partial(layer, m, i, rd))(w, x)
        loads.append(load)
        pairs.append(kept)
        balance, indexer = balance + term, indexer + loss
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    return rd(x) @ rd(weights["head"]).T, jnp.stack(loads), balance, (indexer, jnp.stack(pairs))


def token_loss(logits, targets):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]


@functools.lru_cache(maxsize=None)
def _compiled(model_key: str, control: Optional[str]):
    m = json.loads(model_key)

    def sequence_loss(weights, alpha, x, y):
        logits, load, balance, (indexer, pairs) = forward(m, weights, x, control)
        loss = token_loss(logits, y)
        return loss.mean() + alpha * balance + indexer, (load, loss, balance, indexer, pairs)

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
    """Cross-entropy per token (sequences, length) of held-out sequences (no balance term and no L_I: a validation loss)."""
    with jax.default_matmul_precision("highest"):
        grad = _programs(m, control)[0]  # the one compiled program; its gradients are not looked at here
        weights = jax.tree_util.tree_map(jnp.asarray, weights)
        return np.stack([np.asarray(grad(weights, 0.0, jnp.asarray(xs), jnp.asarray(ys))[0][1][1])
                         for xs, ys in zip(x, y)])


def train(m, weights, batches: Sequence[Tuple[np.ndarray, np.ndarray]], genes: Dict[str, float],
          control: Optional[str] = None) -> Dict[str, Any]:
    """AdamW steps from ``weights`` over ``batches`` (each (x, y) of whole sequences), the first step numbered 0.
    Returns the weights (on the device), AdamW's first moment after the last step (on the host), each step's loss
    (balance term and L_I included), its balance term alone (before ``aux_alpha``), its L_I summed over the layers,
    the pairs each layer kept (layers,) and its load (layers, experts).  ``m["train_steps"]`` sets the warm-up's
    length."""
    with jax.default_matmul_precision("highest"):
        grad, add = _programs(m, control)
        weights = jax.tree_util.tree_map(jnp.asarray, weights)
        paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(weights)[0]]
        moments: Optional[List[Tuple[np.ndarray, np.ndarray]]] = None
        losses, loads, balances, indexer_losses, selected = [], [], [], [], []
        for step, (xb, yb) in enumerate(batches):
            total = load = pairs = None
            loss = balance = indexer = 0.0
            for xs, ys in zip(xb, yb):  # a sequence at a time, gradients added up
                (value, (seq_load, _, seq_balance, seq_indexer, seq_pairs)), g = grad(
                    weights, genes["aux_alpha"], jnp.asarray(xs), jnp.asarray(ys))
                total = g if total is None else add(total, g)
                load = seq_load if load is None else load + seq_load
                pairs = seq_pairs if pairs is None else pairs + seq_pairs
                loss += float(value) / len(xb)
                balance += float(seq_balance) / len(xb)
                indexer += float(seq_indexer) / len(xb)
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
            indexer_losses.append(indexer)
            selected.append(np.asarray(pairs))
            loads.append(np.asarray(load))
        moment = jax.tree_util.tree_unflatten(tree, [mom for mom, _ in moments])
        return {"weights": weights, "moment": moment, "losses": losses, "balances": balances,
                "indexer_losses": indexer_losses, "selected": selected, "loads": loads}
