"""Plain reference of the ``nemotron_h`` family: one rank's share of NVIDIA-Nemotron-3-Super-120B-A12B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json, ``model_type``
``nemotron_h``) in straightforward ``jax.numpy``, float32, every product under
``jax.default_matmul_precision("highest")``.  Imports nothing of ``gentun_tpu`` and takes nothing it has made.

Block ``l`` of type ``t = layer_types[l]`` (``family.model_block`` maps the published ``hybrid_override_pattern``'s
letters: ``M`` mamba2, ``*`` full_attention, ``E`` routed), one sequence ``x`` (length, hidden); ``m`` is the
configuration's model block (the published keys under their published names).  A block is ONE mixer under ONE norm::

    x <- x + Mix_t(RMSNorm(x))        eps layer_norm_epsilon, a weight a channel, no bias; a final RMSNorm, the untied head

    t = mamba2 (Mamba-2): mamba_num_heads heads of mamba_head_dim, n_groups groups of ssm_state_size; head h reads the
        B and C of group h // (heads / groups).  This rank holds heads held_mamba_heads = [first, last), whole groups:
        [z | x | B | C | dt] = u W_in        the held heads' z and x, their groups' B and C, a dt a held head; no bias
        [x | B | C] = silu(conv([x | B | C]) + b_conv)    causal, depthwise, conv_kernel taps:
                      out[t, c] = sum_j kernel[c, j] in[t - (taps - 1) + j, c], zeros before position 0
        D_t = softplus(dt_t + dt_bias);   a_t = exp(D_t * -exp(A_log))                    a head
        a head, S_0 = 0 (head size x state size), ONE POSITION AT A TIME:
            S_t = a_t S_{t-1} + D_t x_t B_t';     y_t = S_t C_t + D x_t                    (D the skip, a head)
        y = RMSNorm(y * silu(z); w_n)        the gate FIRST, then the norm over each group's channels (heads a group x
                                             head size), a weight a channel
        Mix = y W_out                        the held heads' rows of W_out: what the absent heads would add is left out
    t = full_attention: num_attention_heads heads, num_key_value_heads key-value heads, head_dim; no norm of q and k, no
        bias, NO POSITIONAL ENCODING;  o = softmax over the keys j <= i of (q_i . k_j / sqrt(head_dim)) v;  query head n
        uses key-value head n // (heads / kv heads);  Mix = o W_o
    t = routed (a latent mixture of experts; relu2(a) = max(a, 0)^2):
        s = sigmoid(u W_r) over ALL n_routed_experts, float32;  chosen = the num_experts_per_tok largest of (s + b)
        w_e = routed_scaling_factor * s_e / (sum over the chosen of s + 1e-20)            (norm_topk_prob true)
        l = u W_down                          hidden -> moe_latent_size
        Mix = (sum over the HELD experts e of [e chosen] w_e relu2(l W1_e) W2_e) W_up     -- a loop over the held experts
              with a 0/1 mask; no sort, no grouped product; what the absent experts would add is left out --
              + relu2(u W1_s) W2_s            the shared expert, on u, unscaled, whole on every rank
    loss = mean next-token cross-entropy over the held rows of the head; the bias ``b`` chooses and weighs nothing and
           steps outside the gradient: b_e += bias_step * sign(mean load - load_e) over all experts, after each step

Departures from the published model, each noted in the configuration's ``assumed``: no positional encoding (the
Nemotron-H family's convention; ``rope_theta`` and ``partial_rotary_factor`` read as unused defaults;
``m["rotary"]`` turns rope on, for the test that plants it); the multi-token-prediction module is not built; the router
bias's rule is the repo's; ``rescale_prenorm_residual`` is an initialiser's matter and not applied.

Training: mean loss over the batch's tokens, gradients by ``jax.grad``, AdamW written out (beta1 0.9, eps 1e-8,
decoupled decay on every matrix; none on norm weights, ``A_log``, ``D``, ``dt_bias`` and the convolution's bias;
bias-corrected moments, linear warm-up over ``warmup_frac * train_steps`` steps then constant).

Departures from "one array at a time", all of them only so that the published widths fit a 16 GB chip beside the
window's loaded programs, none of them a change of arithmetic: a batch is taken a sequence at a time and the gradients
added up; each block, each held expert, each (head, block of queries) of attention and each block of
``RECURRENCE_BLOCK`` positions of the recurrence is under ``jax.checkpoint``; the loops are ``lax.scan`` / ``lax.map``;
AdamW's two moments live on the host between steps and the update runs leaf by leaf.  The caller frees the program's
state first.

``control="fp8"`` rounds both inputs of every matrix product to float8 e4m3 (the nearest precision below the
configuration's bfloat16): the reference itself in a lower precision, put in the program's place by the check to show
that its limits would catch one.  The recurrence's own products stay float32 there; its inputs come from rounded
products.

The weight tree mirrors the program's parameter tree name for name (``embed``, ``head``, ``final_norm``, ``layers[i]``
with ``op_norm`` and ``mamba`` (``in_proj``, ``kernel``, ``conv_bias``, ``A_log``, ``D``, ``dt_bias``, ``norm``,
``out``) or ``attn`` (``q``, ``k``, ``v``, ``o``), or ``ffn_norm`` and ``moe`` (``router``, ``latent_in``, ``w1``,
``w2``, ``latent_out``, ``shared`` (``w1``, ``w2``))), every matrix as (inputs, outputs): a contract of shapes, stated
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

BETA1, ADAM_EPS, ROUTE_EPS, INIT_STD = 0.9, 1e-8, 1e-20, 0.02
#: Queries whose scores against every key are alive at once, a head: 1,024 x 8,192 float32 = 34 MB.
QUERY_BLOCK = 1024
#: Positions of the recurrence whose states the backward pass holds at once: 128 x 16 heads x 64 x 128 float32 = 67 MB.
RECURRENCE_BLOCK = 128
#: Leaves that are no matrix and take no weight decay (matched against a leaf's own key).
UNDECAYED = ("norm", "A_log", "dt_bias", "conv_bias", "['D']")


def routed_layers(m: Dict[str, Any]) -> List[int]:
    """The layers kept (by their place among them) that are a routed feed-forward."""
    return [i for i, kind in enumerate(m["layer_types"]) if kind == "routed"]


def mamba_share(m: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """(heads held, groups held, the channels of x -- and of z --, the channels of B -- and of C --) of a mamba2 layer."""
    first, last = m["held_mamba_heads"]
    per_group = m["mamba_num_heads"] // m["n_groups"]
    assert first % per_group == 0 and last % per_group == 0 and last > first, (first, last, per_group)
    heads, groups = last - first, (last - first) // per_group
    return heads, groups, heads * m["mamba_head_dim"], groups * m["ssm_state_size"]


def seeded_weights(m: Dict[str, Any], seed: int, std: float = INIT_STD, router_gain: float = 1.0,
                   embed_std: Optional[float] = None, out_std: Optional[float] = None,
                   conv_std: Optional[float] = None) -> Dict[str, Any]:
    """Weights from the seed, numpy float32: normal(0, std), 0.02 at the published widths (a rehearsal's narrow
    layers take a larger one); norm weights, the skip ``D`` and the convolution's bias 1 + normal(0, std) (0 +
    for the bias), so that one applied wrongly shows; ``A_log`` the log of a rate uniform on (1, 16) and ``dt_bias``
    the inverse softplus of a step log-uniform on (time_step_min, time_step_max), the published initialiser's; the
    router ``router_gain`` times wider, so that its sigmoids are far from a half.  ``embed_std``, ``out_std`` (the
    matrices that write into the residual stream: the mixers' output products, the latent up-projection and the
    shared expert's W2) and ``conv_std`` (the convolutions' kernels: four taps of 0.02 would shrink x, B and C
    fifty-fold) are each ``std`` unless given."""
    rng = np.random.default_rng([seed, 0x4E48])
    h, hd = m["hidden_size"], m["head_dim"]
    held = m["held_experts"][1] - m["held_experts"][0]
    nh, nkv, f, fs, lat = (m["num_attention_heads"], m["num_key_value_heads"], m["moe_intermediate_size"],
                           m["moe_shared_expert_intermediate_size"], m["moe_latent_size"])
    heads, _, inner, bc = mamba_share(m)
    embed_std, out_std, conv_std = (std if given is None else given for given in (embed_std, out_std, conv_std))
    mat = lambda *shape, std=std: (std * rng.standard_normal(shape, dtype=np.float32))
    norm = lambda n: (1.0 + mat(n)).astype(np.float32)
    layers = []
    for kind in m["layer_types"]:
        if kind == "mamba2":
            step = np.maximum(np.exp(rng.uniform(math.log(m["time_step_min"]), math.log(m["time_step_max"]), heads)),
                              m["time_step_floor"])
            layer = {"op_norm": norm(h),
                     "mamba": {"in_proj": mat(h, 2 * inner + 2 * bc + heads),
                               "kernel": mat(inner + 2 * bc, m["conv_kernel"], std=conv_std), "conv_bias": mat(inner + 2 * bc),
                               "A_log": np.log(rng.uniform(1.0, 16.0, heads)).astype(np.float32), "D": norm(heads),
                               "dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32),
                               "norm": norm(inner), "out": mat(inner, h, std=out_std)}}
        elif kind == "full_attention":
            layer = {"op_norm": norm(h), "attn": {"q": mat(h, nh * hd), "k": mat(h, nkv * hd), "v": mat(h, nkv * hd),
                                                  "o": mat(nh * hd, h, std=out_std)}}
        else:
            assert kind == "routed", kind
            layer = {"ffn_norm": norm(h),
                     "moe": {"router": router_gain * mat(h, m["n_routed_experts"]), "latent_in": mat(h, lat),
                             "w1": mat(held, lat, f), "w2": mat(held, f, lat), "latent_out": mat(lat, h, std=out_std),
                             "shared": {"w1": mat(h, fs), "w2": mat(fs, h, std=out_std)}}}
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


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def rope(x, theta: float):
    """x (length, heads, head size): rotate-half on every column.  The published model has none (module docstring);
    ``m["rotary"]`` applies it, for the test that plants a rotary encoding in the program."""
    half = x.shape[-1] // 2
    freq = 1.0 / float(theta) ** (np.arange(half, dtype=np.float64) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(w, x, m, rd):
    """One sequence (length, hidden) through causal grouped-query attention without a positional encoding."""
    length = x.shape[0]
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (rd(x) @ rd(w["q"])).reshape(length, nh, hd)
    k = (rd(x) @ rd(w["k"])).reshape(length, nkv, hd)
    v = (rd(x) @ rd(w["v"])).reshape(length, nkv, hd)
    if m.get("rotary"):
        q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
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
    return rd(out.swapaxes(0, 1).reshape(length, nh * hd)) @ rd(w["o"])


def recurrence(x, b, c, step, decay):
    """The state-space recurrence one position at a time: x (length, heads, head size), b, c (length, heads, state
    size), step and decay (length, heads).  Returns y (length, heads, head size), the skip not in it."""
    length, heads, size = x.shape

    def one_position(state, at):
        x_t, b_t, c_t, step_t, decay_t = at
        state = decay_t[:, None, None] * state + jnp.einsum("hp,hn->hpn", step_t[:, None] * x_t, b_t)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def one_block(state, block):
        return jax.lax.scan(one_position, state, block)

    block = min(RECURRENCE_BLOCK, length)
    pad = -length % block
    # positions past the end write nothing (step 0) and are dropped
    blocks = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape((-1, block) + a.shape[1:])
                   for a in (x, b, c, step, decay))
    _, out = jax.lax.scan(one_block, jnp.zeros((heads, size, b.shape[-1]), jnp.float32), blocks)
    return out.reshape((-1,) + out.shape[2:])[:length]


def causal_conv(x, kernel):
    """x (length, channels), kernel (channels, taps): out[t] = sum_j kernel[:, j] x[t - (taps - 1) + j]."""
    taps = kernel.shape[1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(kernel[:, j] * padded[j:j + x.shape[0]] for j in range(taps))


def mamba2(w, x, m, rd):
    """One sequence (length, hidden) through the held heads' share of the Mamba-2 mixer."""
    length = x.shape[0]
    heads, groups, inner, bc = mamba_share(m)
    size, state = m["mamba_head_dim"], m["ssm_state_size"]
    zxbcdt = rd(x) @ rd(w["in_proj"])
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * bc], zxbcdt[:, 2 * inner + 2 * bc:]
    xbc = jax.nn.silu(causal_conv(xbc, w["kernel"]) + w["conv_bias"])
    u = xbc[:, :inner].reshape(length, heads, size)
    b, c = (xbc[:, lo:lo + bc].reshape(length, groups, state) for lo in (inner, inner + bc))
    b, c = jnp.repeat(b, heads // groups, axis=1), jnp.repeat(c, heads // groups, axis=1)  # head h <- group h // (heads / groups)
    step = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(u, b, c, step, jnp.exp(step * -jnp.exp(w["A_log"]))) + w["D"][:, None] * u
    gated = (y.reshape(length, inner) * jax.nn.silu(z)).reshape(length, groups, inner // groups)
    y = rms_norm(gated, w["norm"].reshape(groups, inner // groups), m["layer_norm_epsilon"])
    return rd(y.reshape(length, inner)) @ rd(w["out"])


def routed_ffn(w, bias, x, m, rd):
    """One sequence: (the held experts' part of the sum, in the latent state, times the scaling factor and projected
    up, plus the shared expert; the load of ALL experts)."""
    experts, k = m["n_routed_experts"], m["num_experts_per_tok"]
    scores = jax.nn.sigmoid(rd(x) @ rd(w["router"]))
    _, chosen = jax.lax.top_k(scores + bias, k)  # the bias chooses and weighs nothing
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = m["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS)  # over the chosen, held or not
    first, last = m["held_experts"]
    latent = rd(x) @ rd(w["latent_in"])

    @jax.checkpoint
    def add_expert(out, expert):  # every token through the expert; the 0/1 mask keeps the tokens that chose it
        e, w1, w2 = expert
        mine = (chosen == e).astype(x.dtype)
        return out + (mine * weight).sum(-1, keepdims=True) * (rd(relu2(rd(latent) @ rd(w1))) @ rd(w2)), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(latent), (jnp.arange(first, last), w["w1"], w["w2"]))
    out = rd(out) @ rd(w["latent_out"])
    if m.get("shared_expert", True):  # False: the routed part alone (what the shares-add-up test takes apart)
        out = out + rd(relu2(rd(x) @ rd(w["shared"]["w1"]))) @ rd(w["shared"]["w2"])
    return out, (chosen[..., None] == jnp.arange(experts)).sum((0, 1))


def layer(m, index: int, rd, w, bias, x):
    """One sequence through block ``index`` (of the layers kept): (output, load or None).  One norm, one mixer."""
    kind, eps = m["layer_types"][index], m["layer_norm_epsilon"]
    if kind == "routed":
        out, load = routed_ffn(w["moe"], bias, rms_norm(x, w["ffn_norm"], eps), m, rd)
        return x + out, load
    normed = rms_norm(x, w["op_norm"], eps)
    if kind == "mamba2":
        return x + mamba2(w["mamba"], normed, m, rd), None
    assert kind == "full_attention", kind
    return x + attention(w["attn"], normed, m, rd), None


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
    x = rms_norm(x, weights["final_norm"], m["layer_norm_epsilon"])
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
    return np.zeros((len(routed_layers(m)), m["n_routed_experts"]), np.float32)


def eval_token_loss(m, weights, bias, x: np.ndarray, y: np.ndarray, control: Optional[str] = None) -> np.ndarray:
    """Cross-entropy per token (sequences, length) of held-out sequences under the router bias ``bias``."""
    with jax.default_matmul_precision("highest"):
        grad = _programs(m, control)[0]  # the one compiled program; its gradients are not looked at here
        weights, bias = jax.tree_util.tree_map(jnp.asarray, weights), jnp.asarray(bias)
        return np.stack([np.asarray(grad(weights, bias, jnp.asarray(xs), jnp.asarray(ys))[0][1][1])
                         for xs, ys in zip(x, y)])


def train(m, weights, batches: Sequence[Tuple[np.ndarray, np.ndarray]], genes: Dict[str, float],
          control: Optional[str] = None, bias: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """AdamW steps from ``weights`` over ``batches`` (each (x, y) of whole sequences), the first step numbered 0,
    from the router bias ``bias`` (zeros if None).  Returns the weights (on the device), AdamW's first moment
    after the last step (on the host), the router bias after its last step, each step's loss and its load (routed
    layers, experts).  ``m["train_steps"]`` sets the warm-up's length."""
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
                                          decay=not any(name in str(path[-1]) for name in UNDECAYED))
                leaves[i], grads[i] = p, None
                moments[i] = (np.asarray(mom), np.asarray(var))
            weights = jax.tree_util.tree_unflatten(tree, leaves)
            mean_load = len(xb) * xb.shape[1] * m["num_experts_per_tok"] / m["n_routed_experts"]
            bias = bias + genes["bias_step"] * jnp.sign(mean_load - load.astype(jnp.float32))
            losses.append(loss)
            loads.append(np.asarray(load))
        moment = jax.tree_util.tree_unflatten(tree, [mom for mom, _ in moments])
        return {"weights": weights, "moment": moment, "bias": np.asarray(bias), "losses": losses, "loads": loads}
