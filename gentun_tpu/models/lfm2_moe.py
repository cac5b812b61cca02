"""Routed language models as fitness models: one expert-parallel rank's share, trained under a recipe genome.

The second jax family beside the Genetic-CNN (``models/cnn.py``), and seven
architectures of it, told apart by the configuration alone (which operator a
layer has -- or whether it is an operator alone or a feed-forward alone --,
which mask -- or whether the mask is data, a learned indexer's choice of keys --,
which rope on how many of a head's columns and how many query heads
an attention layer's type gives it, whether its output passes a gate, how the
router scores, whether the experts are gated and in which state they work,
whether shared experts stand beside the routed ones and behind a gate, whether
the head is tied, which balance rule runs): one evaluator, one train step
builder, one expert layer, one causal core and one optimizer serve all.

``LFM2-24B-A2B`` (``model_type`` ``lfm2_moe``,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json; the
defaults of :class:`Lfm2MoeConfig`): gated
short convolutions and grouped-query attention as operators, a dense SwiGLU
feed-forward in the leading layers and 64 routed experts, 4 per token, in the
others.  Layer ``l``, input ``x`` of shape (tokens, hidden)::

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))
    Op = conv:  B, C, u = split3(W_in x);  W_out (C * causal_depthwise_conv1d_L(B * u))
    Op = full_attention:  q, k, v = W_q x, W_k x, W_v x; RMSNorm over the head size on q and k;
         rope on q, k; causal softmax(q k' / sqrt(head)) v, each key-value head serving
         heads / kv_heads query heads; W_o.  The causal core is one function with two
         programs: on a TPU, at a length of whole kernel blocks, a fused kernel (splash
         attention, online softmax, its own backward: no score reaches memory); anywhere
         else XLA's query blocks of ``attn_block`` (``_attention``)
    FFN dense:   W_2 (silu(W_1 x) * W_3 x)
    FFN routed:  s = sigmoid(W_r x); choose top-k of (s + b); w = s[chosen] / (sum s[chosen] + 1e-6);
                 out = sum over chosen AND held experts e of  w_e W2_e (silu(W1_e x) * W3_e x)
    output: RMSNorm, logits over the held rows of the tied embedding, mean next-token cross-entropy

``DeepSeek-V2-Lite`` (``model_type`` ``deepseek_v2``,
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json): every
layer's operator is latent attention, every routed layer adds shared experts, the
router is a softmax whose chosen probabilities are the weights as they are, and
balance comes from a term of the loss::

    Op = latent_attention:  q = W_q x -> heads x (nope + rope);  [c ; k_pe] = W_kva x -> kv_lora_rank + rope;
         c = RMSNorm(c);  [k_nope ; v] = W_kvb c -> heads x (nope + v);  rope (YaRN frequencies) on q_pe and
         on k_pe, which is ONE head shared by every query head;
         causal softmax((q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5 * m^2) v;  W_o
         (m = 0.1 * mscale_all_dim * ln factor + 1).  The same two cores as above run it, at a head
         size of nope + rope for q and k and of v for the values
    FFN routed:  p = softmax(W_r x) in float32; chosen = top-k of p; w = p[chosen], un-normalised;
                 out = sum over chosen AND held e of w_e W2_e (silu(W1_e x) * W3_e x)
                     + W2_s (silu(W1_s x) * W3_s x)          the shared experts, one SwiGLU of their joint width
    loss = cross-entropy (head untied) + alpha * sum over routed layers of
           mean over sequences of sum_e f_e P_e, over ALL experts:  f_e = experts / (k L) * #(tokens of the
           sequence that chose e), a count without a gradient; P_e = the sequence's mean of p_e (``aux_alpha``
           is the recipe's fifth gene where LFM2 has ``bias_step``; no bias, no rule outside the gradient)

``Mellum2-12B-A2.5B-Instruct`` (``model_type`` ``mellum``,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
``layer_types`` mixes ``sliding_attention`` and ``full_attention`` 3:1, and the
layer's type picks its mask and its rope; the head size is stated (32 heads of
128 on a hidden size of 2304), q and k have no norm, every layer is routed (no
dense layer, no shared expert), 8 of 64 experts a token with their weights
normalised over the chosen::

    Op = sliding_attention:  GQA as above without the norm of q and k; key j visible to query i iff
         0 <= i - j <= sliding_window - 1; rope at theta^(-2c/head) (``rope_parameters.sliding_attention``)
    Op = full_attention:     key j visible iff j <= i; YaRN's frequencies, cos and sin times ``attention_factor``
         (``rope_parameters.full_attention``).  The same two cores run both masks: the fused kernel is handed
         the library's ``CausalMask`` and visits only the block pairs that hold a visible key (36 of 64 at
         8,192 positions and 1,024 x 1,024 blocks), or, under the window, runs banded: the sequence in chunks,
         a chunk's queries of a key-value head's whole group as one block of rows against the chunk's own
         ``chunk + window`` keys under the library's ``LocalMask`` (:func:`_banded_core`; a head visits
         ``length x (chunk + window)`` score elements, :func:`_kernel_visits`, where the unbanded kernel's 15
         pairs cost 15.7 M at any window up to 1,024); XLA's query blocks are handed the window's keys alone
    FFN routed:  p = softmax(W_r x); chosen = top-k; w = p[chosen] / sum p[chosen]; the held experts' part
    loss = cross-entropy (head untied) + alpha * the balance term above: the *recipe's* (``aux_alpha``), the
           published config gives it no weight

``Qwen3-Next-80B-A3B-Instruct`` (``model_type`` ``qwen3_next``,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json):
``linear_attention`` layers (Gated DeltaNet) three to one with gated
``full_attention`` at a head size of 256, every layer routed (512 experts, 10 a
token, their weights normalised over the chosen) with one shared expert behind
a sigmoid gate::

    Op = linear_attention:  [q ; k ; v ; z] = W_qkvz x (key heads' q and k, value heads' v and z, blocks of
         columns);  [b ; a] = W_ba x, one of each a value head;  [q ; k ; v] = silu(causal depthwise conv of
         ``linear_conv_kernel_dim`` taps, zeros before position 0);  beta = sigmoid(b),
         g = -exp(A_log) softplus(a + dt_bias) <= 0;  q = l2norm(q) / sqrt(key size), k = l2norm(k), each key head
         serving value heads / key heads value heads;  per value head from S_0 = 0, float32:
             S' = exp(g_t) S_{t-1};   S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;   o_t = S_t^T q_t
         o = RMSNorm(o; w_n) * silu(z) a head;  W_out.  The rule runs in chunks of ``delta_chunk`` positions
         (:func:`_delta_core`): a chunk's updates are one unit lower-triangular system, solved once for all chunks;
         a chunk then maps the state that enters it to ``A S + B``, with ``A``, ``B`` and the outputs' operands
         batched products over all chunks, and a ``scan`` carries the state and nothing else from chunk to chunk
         (one product a step, forward and backward: :func:`_affine_scan`); on a TPU at widths of whole lanes the
         same chunks run as two fused kernels that hold a chunk in fast memory and carry the state
         (:mod:`gentun_tpu.models.delta_kernel`), XLA's ops elsewhere
    Op = full_attention (gated):  [q ; gate] = W_q x, a head's columns [its query | its gate];  k, v as above;
         RMSNorm on q and k;  rope on the leading ``partial_rotary_factor`` of a head's columns (rotate-half inside
         them), the others pass;  the causal core;  W_o (o * sigmoid(gate))
    FFN routed:  Mellum2's rule over 512 experts, 10 a token, the held experts' part
                 + sigmoid(w_g . x) * W2_s (silu(W1_s x) * W3_s x)      one shared expert, one gate scalar a token
    loss = cross-entropy (head untied) + alpha * the balance term above (the recipe's)
    ``A_log`` starts at ln u, u uniform on (0, 16), ``dt_bias`` and norm weights at 1; none of the three takes
    weight decay.  The published norms are ``x_hat (1 + w)`` with w from 0: the same function and updates as
    :func:`_rms_norm`'s ``x_hat w`` from 1, which is kept

``Laguna-XS.2`` (``model_type`` ``laguna``,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json): ``full_attention``
and ``sliding_attention`` 1:3, and the layer's type picks its mask, its rope, the
share of a head that rope turns AND its query heads (``num_attention_heads_per_layer``:
48 in a full layer, 64 in a sliding one, 8 key-value heads in both); every head's
output passes a gate of one scalar a head and token; the leading layer's feed-forward
is dense, the others route 8 of 256 experts by LFM2's rule, scale the routed sum and
add one shared expert::

    Op = sliding_attention:  Mellum2's, at ``n_l`` query heads (``n_l / kv_heads`` to a key-value head);
         rope on every column at theta^(-2c/head)
    Op = full_attention:     key j visible iff j <= i; rope on the leading ``partial_rotary_factor`` of a head's
         columns (``rope_parameters.full_attention``; rotate-half inside them, YaRN's frequencies over those
         columns, cos and sin times ``attention_factor``), the others pass
    both:  o_head = o_head * sigmoid(w_head . x)   ``x`` the layer's normed input, ``W_g`` (hidden, n_l) a leaf of
         its own (``gate``), the product and the sigmoid float32;  W_o.  The fused kernel is built a key-value
         head with its group of query heads, so one program holds it at two groups under two masks
    FFN dense (layer 0):  W_2 (silu(W_1 x) * W_3 x)
    FFN routed:  LFM2's rule (a sigmoid each, top-k of s + b, w = s[chosen] / (sum + 1e-6)) over 256 experts;
                 out = ``routed_scaling_factor`` * sum over chosen AND held e of w_e W2_e (silu(W1_e x) * W3_e x)
                     + W2_s (silu(W1_s x) * W3_s x)          the shared expert, unscaled, whole on every rank
    loss = cross-entropy (head untied); the bias ``b`` steps outside the gradient as LFM2's does

``NVIDIA-Nemotron-3-Super-120B-A12B`` (``model_type`` ``nemotron_h``,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json):
a block is ONE of a Mamba-2 mixer, an attention and a routed feed-forward, with one norm
and one residual add (``layer_types`` of ``mamba2``, ``full_attention`` and ``routed``: a
``routed`` entry makes every layer of the model one half, :meth:`Lfm2MoeConfig.halves_of`);
the mixer is held by a share of its heads, whole groups (``held_mamba_heads``), as the
expert layer by a share of its experts; the experts are two matrices under a squared ReLU
and work in a latent state narrower than the residual stream::

    x <- x + Mix(RMSNorm(x))
    Mix = mamba2:  [z | x | B | C | dt] = W_in u, the held heads' z, x and dt and their groups' B and C (head h
         reads group h // (heads / groups));  [x | B | C] = silu(causal depthwise conv of ``mamba_conv_kernel``
         taps + b_conv);  D_t = softplus(dt_t + dt_bias), a_t = exp(-exp(A_log) D_t) a head;  per head from
         S_0 = 0 in R^(head size x state size), float32:
             S_t = a_t S_{t-1} + D_t x_t B_t';   y_t = S_t C_t + D x_t
         y = RMSNorm(y * silu(z); w_n) over each group's channels, the gate first;  W_out[the held heads' rows] y.
         The recurrence runs in chunks of ``mamba_chunk`` positions (:func:`_state_space_core`): with L the
         running sum of ``D_t A`` inside a chunk, Y = ((C B') o exp(L_i - L_j)[i >= j]) (D x) + exp(L_i) C_i S_in,
         and a chunk adds sum_j exp(L_end - L_j) D_j x_j B_j' to exp(L_end) S_in: :func:`_affine_scan`'s
         ``S <- a S + b`` under one scalar ``a`` a head and chunk, everything else batched over all chunks
    Mix = full_attention:  GQA as above, 16 query heads to a key-value head, no norm of q and k and NO
         positional encoding (``positional_encoding`` ``none``: the order reaches the model through the mixers)
    Mix = routed:  s = sigmoid(W_r u) over all experts; chosen = top-k of (s + b);
         w = ``routed_scaling_factor`` * s[chosen] / (sum s[chosen] + 1e-20);  l = W_down u in R^``moe_latent_size``;
         out = W_up (sum over chosen AND held e of w_e W2_e relu2(W1_e l)) + W2_s relu2(W1_s u)
         (relu2(a) = max(a, 0)^2; the router and the shared expert, of its own width, read the full state;
         the row buffer's worst case is min(k, held) x tokens rows: 8 held of 512 under a top-22 is 8 a token)
    loss = cross-entropy (head untied); the bias ``b`` steps outside the gradient as LFM2's does
    ``A_log`` starts at ln u, u uniform on (1, 16), ``D`` at 1, the convolution's bias at 0, ``dt_bias`` at the
    inverse softplus of a step log-uniform on (0.001, 0.1); none of them, nor a norm weight, takes weight decay

``Keye-VL-2.0-30B-A3B``'s language model (``model_type`` ``KeyeVL2``,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json; on text, no vision tower):
every layer is grouped-query attention whose keys a learned indexer chooses (``layer_types`` of
``sparse_attention``; DeepSeek Sparse Attention's indexer, the DeepSeek-V3.2-Exp report, over a GQA trunk),
then 128 routed experts 8 a token by Mellum2's rule.  ``u`` the layer's normed input, ``T`` the
length, ``k`` = ``sparse_topk`` (2,048)::

    trunk:    q = W_q u (32 heads of 128), k, v = W_k u, W_v u (4 heads of 128); RMSNorm over the head size on q
              and k; rope in rotate-half layout whose 64 frequencies read their angle by section from three position
              streams (``mrope_section`` [16, 24, 24]: temporal, then the two spatial; the token's index on text);
              scale 128^-0.5
    indexer:  qI_j = rope(W_qI[j] u) (j = 1..16, 64 wide), kI = rope(W_kI u) (ONE key head for all 16), w = W_w u;
              I[t, s] = 16^-0.5 64^-0.5 sum_j w[t, j] relu(qI_j[t] . kI[s])   for s <= t;   ``u`` DETACHED here
    select:   tau[t] = the k-th largest of I[t, 0..t] (minus infinity where t has no more than k keys), found by
              bisection on the scores' bit pattern (:func:`_kth_largest`: 32 counting passes, exact, no sort);
              S_t = {s <= t : I[t, s] >= tau[t]}: a threshold, so ties are kept; sum_t |S_t| = k (k + 1) / 2 +
              (T - k) k when none ties, counted on the device.  The choice is made once, on the scores the
              threshold was found in, and handed on as bits (:func:`_sparse_selection`: 33.5 MB a layer and
              sequence of 16,384, kept for the backward pass: the rematerialised forward does not select again;
              planes of bits over super-tiles of 4,096 keys, which a kernel unpacks with two integer ops,
              :func:`gentun_tpu.models.sparse_kernel.packed`)
    core:     each of the 32 heads is softmax attention over S_t alone: a mask that is data.  One function with two
              programs: on a TPU, at a shape the kernels take (:func:`_use_sparse_kernel`), three fused kernels of the
              repo's own (:mod:`gentun_tpu.models.sparse_kernel`, :func:`_sparse_kernel_core`: forward with online
              softmax, its own backward, and the heads' share for L_I; they read the choice as bits, walk every tile
              up to the diagonal, and no score reaches memory); anywhere else XLA's query blocks (:func:`_sparse_core`:
              groups of four blocks of ``attn_block``, each against the keys up to its group's last query; no (heads x
              T x T) array is ever alive), which are also what the kernels are tested against; W_o
    L_I:      p[t, s] = (1 / 32) sum_h prob_h[t, s] on S_t, a constant to the gradient;
              L_I = mean_t sum_{s in S_t} p log(p / softmax_{S_t}(I[t, :])), one a layer (DeepSeek-V3.2's sparse
              training stage).  W_qI, W_kI and W_w get their gradient from L_I alone; nothing else gets any from it
    loss = cross-entropy (head untied) + alpha * the balance term (the recipe's ``aux_alpha``) + sum over layers of L_I
    the fitness is the held-out cross-entropy alone

What differs from the CNN family, by design:

- **Genes are data, not structure.**  Every individual is the same
  architecture; the genome is the training recipe (``genes.lfm2_moe_genome``:
  learning rate, warm-up, weight decay, Adam's beta2, the router bias's step).
  One compiled train step and one compiled eval program serve every genome;
  genes and the step number enter as scalar arguments.
- **The expert layer is told which experts it holds** (``held_experts``, a
  range; the router keeps ``num_experts`` outputs).  It routes over all of
  them, keeps the (row, expert, weight) triples of its own experts in a row
  buffer whose height follows the rows present -- the shortest of a ladder of
  heights that holds them (``_ROW_BUFFER_SHARES`` times the rank's mean share,
  then the worst case of top-k x tokens), decided on the device from the
  router's own count (``_moe_ffn``): gather, masks and the float32 scatter-add
  all run at the buffer's height, so a rung nearer the rows saves their time --
  runs the three grouped products with a kernel whose cost follows the rows
  present (megablox ``gmm`` on a TPU, ``lax.ragged_dot`` elsewhere), and
  returns its experts' part of the sum.  What the absent experts would add is
  left out; no code stands in for the other chips.  No assignment is dropped at
  any height (``dropped`` is counted, and so is the height every layer took).
- **A program is one individual wide.**  One individual of the published cut
  is ~0.65 B parameters and 16 bytes of training state a parameter
  (:func:`training_bytes`); two do not fit a 16 GB chip, so a population is
  scored one after another on the one compiled program, the train state is
  donated from step to step and freed between individuals.  That is known from
  a count of bytes before anything compiles, not from a failed attempt.
- **Fitness is a negative validation loss** (higher is better, like an
  accuracy), a pure function of genome, configuration and seed: the state is
  built from the genome's content hash (``evaluation.genome_hashes``), the batches
  from the seed.

Parameters are float32, compute is bfloat16 (router, norms, softmax, logits
and loss float32; of a ``linear_attention`` layer also its gates, its
convolution's arithmetic, the l2 norms, the state and every product of the
delta rule's core; of a ``mamba2`` layer its step and decay, its convolution's
arithmetic, the state and every product of its core, the gate and its norm; of a ``sparse_attention`` layer's
indexer the relu, the weights, the sum over its heads, the threshold, the comparison and the KL term -- its products
are the compute dtype's, accumulated in float32).  The router bias ``b`` is not trained by the gradient:
after each step ``b_e += u * sign(mean load - load_e)`` over all experts
(arXiv:2408.15664; ``u`` is the ``bias_step`` gene).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Mapping, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import spans as _tele
from ..telemetry.registry import get_registry as _get_registry
from .evaluation import base_keys, evaluation_prelude, genome_hashes, phase
from .generic import GentunModel

__all__ = ["Lfm2MoeModel", "Lfm2MoeConfig", "Lfm2MoePrograms", "GENE_NAMES", "gene_names", "training_bytes",
           "selected_keys"]

#: The recipe's genes in the order the compiled programs take them (one float32 vector); the
#: fifth belongs to the balance rule (:func:`gene_names`), and this is the bias rule's form.
GENE_NAMES = ("log10_lr", "warmup_frac", "weight_decay", "beta2", "bias_step")
#: The fifth gene by balance rule: the bias's step (arXiv:2408.15664) or the weight of the loss's balance term.
_BALANCE_GENE = {"bias": "bias_step", "aux_loss": "aux_alpha"}
ADAM_BETA1, ADAM_EPS, INIT_STD, ROUTE_EPS = 0.9, 1e-8, 0.02, 1e-6
#: Under the root of a ``linear_attention`` layer's l2 norm of q and k; the largest decay rate ``exp(A_log)`` starts at.
L2_EPS, DECAY_RATE_MAX = 1e-6, 16.0
#: Leaves that are no matrix: they start from a value of their own (:func:`_init_leaf`) and take no weight decay
#: (a ``mamba2`` layer's skip ``D`` and its convolution's bias among them; matched against a leaf's own key).
_UNDECAYED = ("norm", "A_log", "dt_bias", "conv_bias", "['D']")
#: A ``mamba2`` layer's start: the decay rate ``exp(A_log)`` uniform on (1, ``DECAY_RATE_MAX``); the step
#: ``softplus(dt_bias)`` log-uniform on (``time_step_min``, ``time_step_max``), floored at ``time_step_floor``.
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 1e-3, 0.1, 1e-4
#: megablox tiles (rows, contraction, columns); the row tile shrinks to divide a small buffer
#: (:func:`_gmm_tiling` follows the shape from here).
_GMM_TILING = (512, 512, 512)
#: The fused attention kernel's blocks (splash attention): queries x keys a grid step holds and
#: the keys one product inside it takes, forward, then the same for the one backward kernel
#: (dk, dv and dq together).  Set by chip runs at the published shape (PERF.md, PR 31).
#: A layer whose mask is a window runs banded where its shape allows (:func:`_kernel_chunk`) and
#: takes these blocks where not: the unbanded kernel visits the block pairs that hold a visible
#: key and costs each its whole area (15 of 64 at a window of 512 and of 1,024 alike), and
#: smaller blocks waste less of it (45 of 256 pairs at 512 x 512, a quarter less area) but pay
#: more grid steps: forward and backward of one layer-step at Mellum2's shape took 16.8 ms at
#: these blocks, 20.4 at 512 / 512 / 512, 18.0-21.2 at four other shapes (PERF.md, PR 34).
_ATTN_KERNEL_BLOCKS = dict(block_q=1024, block_kv=1024, block_kv_compute=512,
                           block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512)
#: The columns of a head of q (as wide as k's, zero columns counted) and of v together up to which the
#: backward kernel holds those blocks in the chip's fast memory: 256 + 128 (latent attention) does, 256 + 256
#: (a head size of 256) asks for 16.57 MB of the 16 it may take, and its query block halves
#: (:func:`_kernel_blocks`).  The key block stays: the fused backward writes a partial dq a key block.
_ATTN_KERNEL_COLUMNS = 384
#: The chunks a windowed layer's banded core may take, the first that fits the shape (:func:`_kernel_chunk`),
#: and the widest product inside its one key block (:func:`_band_blocks`).
_ATTN_KERNEL_CHUNKS = (256, 128, 512)
_ATTN_BAND_COMPUTE = 768
#: The (queries, keys) a grid step of the masked core's three kernels holds (:mod:`gentun_tpu.models.sparse_kernel`: a
#: ``sparse_attention`` layer's forward, backward and heads' share), all the query heads of a key-value head in it.  Set by
#: chip runs at the published shape (8 query heads a key-value head, 16,384 positions of 128 columns), each kernel timed
#: alone over six tiles (``scripts/sparse_core_study.py``; PERF.md section 5 has the table): the backward and the share were
#: fastest at this one, the forward 2% faster at 1,024 x 1,024, where the backward is 22% slower: they share one.
_SPARSE_KERNEL_TILE = (512, 1024)
#: The row buffer's heights below the worst case (top-k x tokens, always the last rung), in
#: shares: times the rows a routed layer sends this rank on average.  A layer-step runs at the
#: first that holds its rows; dispatch, combine and the experts' masks cost their height.
#: A rung is also one more copy of the experts' kernels in every routed layer, forward and
#: backward: 0.11-0.22 GB of device memory for its code, 20-30 s of a cold set-up and, until a
#: rung's body was traced once a program, 4-6 s of a warm one.  So the ladder has the one rung
#: below PR 29's 2.75 that the rows asked for: six heights (1, 1.5, 2, 2.75, 4 shares, the worst
#: case) showed 96% of LFM2's layer-steps and 64% of Mellum2's under 1.25 shares, and ran 60 and
#: 100 shares an individual where these run 62 and 123 and PR 29's two ran 132 and 185 (PERF.md).
_ROW_BUFFER_SHARES = (1.25, 2.75)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Everything the compiled programs are specialised on (hashable: the
    lru-cache key of :func:`_programs`).  Defaults are the published widths and
    the one-chip cut of ``benchmark/configs/lfm2_24b_a2b_ep8.json``."""

    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv")
    layer_ids: Tuple[int, ...] = (0, 2, 3, 4, 5, 6, 7)  # the published indices, for scope names
    num_dense_layers: int = 1
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    held_experts: Tuple[int, int] = (0, 8)  # [first, last) of the router's outputs
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    vocab_size: int = 8192  # the rows of the vocabulary held here
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    seq_len: int = 4096
    batch_sequences: int = 4
    train_steps: int = 8
    eval_sequences: int = 8
    n_sequences: int = 40
    attn_block: int = 512
    compute_dtype: str = "bfloat16"
    # what a second architecture sets (the defaults are LFM2's): a ``latent_attention`` layer's
    # ranks and head sizes and its rope's scaling (the published block as sorted items, or None)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    n_shared_experts: int = 0  # shared experts beside the routed ones, one SwiGLU of their joint width
    scoring_func: str = "sigmoid"  # or "softmax"
    norm_topk_prob: bool = True  # the chosen weights divided by their sum
    balance_rule: str = "bias"  # a router bias stepped outside the gradient, or "aux_loss": a term of the loss
    tie_word_embeddings: bool = True
    # what a third architecture sets (Mellum2): a head size that is stated (0: hidden_size over the heads), no
    # per-head norm of q and k, ``sliding_attention`` layers' window (a query's own position counts) and rope
    # by layer type (``rope_parameters``: (layer type, the published block as sorted items) pairs, or None)
    head_dim: int = 0
    qk_norm: bool = True
    sliding_window: int = 0
    rope_parameters: Optional[Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...]] = None
    # what a fourth architecture sets (Qwen3-Next): a ``linear_attention`` layer's heads (key heads, each serving
    # value heads / key heads value heads), their sizes, its convolution's taps and the positions a chunk of its
    # scan holds; the share of a head's columns that rope turns; a sigmoid gate on the attention's output (the q
    # projection is twice as wide) and one on the shared expert (a scalar a token)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    delta_chunk: int = 64
    partial_rotary_factor: float = 1.0
    attn_output_gate: bool = False
    shared_expert_gate: bool = False
    # what a fifth architecture sets (Laguna-XS.2): query heads by layer (one count a layer of ``layer_types``, or
    # None: ``num_attention_heads`` in each; the key-value heads are one count); the share of a head that rope turns
    # by layer type (a ``partial_rotary_factor`` inside a ``rope_parameters`` block, :meth:`rotary_of`); a sigmoid
    # gate of one scalar a head and token on the attention's output, from a projection of its own (``gate``: hidden
    # x heads); the factor on the routed experts' sum (the shared experts' output is added unscaled)
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    attn_head_gate: bool = False
    routed_scaling_factor: float = 1.0
    # what a sixth architecture sets (Nemotron-H): layers that are a mixer ALONE or a feed-forward ALONE (a
    # ``layer_types`` entry of ``routed`` is a routed feed-forward with its one norm and no mixer, and every other
    # layer of such a model a mixer with its one norm and no feed-forward: :meth:`halves_of`); a ``mamba2`` layer's
    # published heads, groups and sizes and the heads of them held here (``held_mamba_heads``, whole groups, as
    # ``held_experts`` is a range of the router's outputs); attention without a positional encoding; experts of two
    # matrices under a squared ReLU (``mlp_hidden_act`` ``relu2``: no gate; ``silu`` is the SwiGLU) that work in a
    # latent state of ``moe_latent_size`` channels between a down- and an up-projection (0: in the residual
    # stream's); a shared expert of a width of its own (0: ``n_shared_experts`` x ``moe_intermediate_size``); what
    # guards the sum the chosen sigmoids are divided by
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 0
    ssm_state_size: int = 0
    mamba_conv_kernel: int = 4
    mamba_chunk: int = 128
    held_mamba_heads: Optional[Tuple[int, int]] = None  # [first, last) of the published heads; None: all
    positional_encoding: str = "rope"  # or "none"
    mlp_hidden_act: str = "silu"  # or "relu2"
    moe_latent_size: int = 0
    shared_expert_intermediate_size: int = 0
    route_eps: float = ROUTE_EPS
    # what a seventh architecture sets (Keye-VL-2.0's language model): a ``sparse_attention`` layer's indexer (its
    # heads, their size -- they share ONE key head -- and the keys it keeps a query, ``sa_config``'s
    # ``indexer_num_heads``, ``indexer_head_dim`` and ``topk``), and rope whose frequencies are taken by section from
    # several position streams (``rope_scaling.mrope_section``: how many of a head's rotated pairs read each stream;
    # None: one stream, the token's index)
    indexer_num_heads: int = 0
    indexer_head_dim: int = 0
    sparse_topk: int = 0
    mrope_section: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if not self.head_dim:  # frozen: the stated size takes the place of the implied one once, here
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_attention_heads)

    @property
    def gene_names(self) -> Tuple[str, ...]:
        return gene_names(self.balance_rule)

    @property
    def yarn(self) -> Optional[Dict[str, Any]]:
        """``rope_scaling`` as the mapping it was given as, or None."""
        return dict(self.rope_scaling) if self.rope_scaling else None

    def rope_of(self, kind: str) -> Tuple[float, Optional[Dict[str, Any]]]:
        """(theta, YaRN's block or None) of a layer of type ``kind``: ``rope_parameters``' entry for
        it where the configuration keys its rope by layer type, else ``rope_theta`` and no scaling."""
        block = dict(dict(self.rope_parameters or ()).get(kind, ()))
        if not block:
            return self.rope_theta, None
        return float(block["rope_theta"]), block if block.get("rope_type", "default") == "yarn" else None

    def window_of(self, kind: str) -> Optional[int]:
        """The keys a query of a ``kind`` layer sees, its own position counted, or None for all before it."""
        return self.sliding_window if kind == "sliding_attention" else None

    @property
    def typed_attention(self) -> bool:
        """Whether attention layers are told apart by type (their scope is the type's name, with
        ``proj``, ``rope`` and ``core`` inside); LFM2's one kind keeps its one ``attention`` scope."""
        return bool({"sliding_attention", "linear_attention", "mamba2", "routed", "sparse_attention"} & set(self.layer_types))

    @property
    def rotary_dim(self) -> int:
        """The leading columns of a head of q and k that rope turns (all of them at a factor of 1)."""
        return int(self.head_dim * self.partial_rotary_factor)

    def rotary_of(self, kind: str) -> int:
        """The leading columns of a head that the rope of a ``kind`` layer turns: the share its
        ``rope_parameters`` block states (``partial_rotary_factor``), else the configuration's one share."""
        share = dict(dict(self.rope_parameters or ()).get(kind, ())).get("partial_rotary_factor")
        return self.rotary_dim if share is None else int(self.head_dim * share)

    def heads_of(self, index: int) -> int:
        """The query heads of the ``index``-th layer kept (its attention's; every layer's where one count serves all)."""
        per_layer = self.num_attention_heads_per_layer
        return self.num_attention_heads if per_layer is None else per_layer[index]

    @property
    def n_held(self) -> int:
        return self.held_experts[1] - self.held_experts[0]

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        """The layers kept (by their place among them) whose attention's keys an indexer chooses, in order."""
        return tuple(i for i, kind in enumerate(self.layer_types) if kind == "sparse_attention")

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        """The layers kept (by their place among them) whose feed-forward is routed, in order."""
        if self.single_half_layers:
            return tuple(i for i, kind in enumerate(self.layer_types) if kind == "routed")
        return tuple(range(self.num_dense_layers, len(self.layer_types)))

    @property
    def single_half_layers(self) -> bool:
        """Whether a layer is ONE of a mixer and a feed-forward under one norm (a model with ``routed`` layers)."""
        return "routed" in self.layer_types

    def halves_of(self, index: int) -> Tuple[str, ...]:
        """What the ``index``-th layer kept has of ``("mixer", "ffn")``: both, or the one its type names."""
        if not self.single_half_layers:
            return ("mixer", "ffn")
        return ("ffn",) if self.layer_types[index] == "routed" else ("mixer",)

    @property
    def gated_experts(self) -> bool:
        """Whether a feed-forward is a SwiGLU (three matrices) or ``W_2 relu2(W_1 x)`` (two)."""
        return self.mlp_hidden_act == "silu"

    @property
    def mamba_heads(self) -> Tuple[int, int]:
        """The ``mamba2`` heads held here as [first, last) of the published ones."""
        return self.held_mamba_heads or (0, self.mamba_num_heads)

    @property
    def mamba_held(self) -> Tuple[int, int]:
        """(heads held, groups held) of a ``mamba2`` layer: a group is ``mamba_num_heads / mamba_n_groups`` heads
        with their one B and C."""
        heads = self.mamba_heads[1] - self.mamba_heads[0]
        return heads, heads * self.mamba_n_groups // self.mamba_num_heads

    @property
    def tokens_per_step(self) -> int:
        return self.batch_sequences * self.seq_len


# -- the count of bytes that sets the program's width ---------------------------------------


def param_shapes(cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """The parameter tree as shapes: what ``init`` fills and the reference mirrors."""
    h, hd = cfg.hidden_size, cfg.head_dim
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        halves = cfg.halves_of(i)  # a layer has a norm for each half it has
        layer: Dict[str, Any] = {f"{'op' if half == 'mixer' else 'ffn'}_norm": (h,) for half in halves}
        if "mixer" not in halves:
            pass
        elif kind == "conv":
            layer["conv"] = {"in_proj": (h, 3 * h), "kernel": (h, cfg.conv_L_cache), "out_proj": (h, h)}
        elif kind == "latent_attention":
            nh, rank, nope, rope, vd = (cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                                        cfg.qk_rope_head_dim, cfg.v_head_dim)
            layer["latent"] = {"q": (h, nh * (nope + rope)), "kva": (h, rank + rope), "kv_norm": (rank,),
                               "kvb": (rank, nh * (nope + vd)), "o": (nh * vd, h)}
        elif kind == "linear_attention":
            nv, keys, values = cfg.linear_num_value_heads, *_delta_widths(cfg)
            layer["delta"] = {"qkvz": (h, 2 * keys + 2 * values), "ba": (h, 2 * nv),
                              "kernel": (2 * keys + values, cfg.linear_conv_kernel_dim), "A_log": (nv,),
                              "dt_bias": (nv,), "norm": (cfg.linear_value_head_dim,), "out": (values, h)}
        elif kind == "mamba2":
            # the share's: the held heads' z, x and dt columns of ``W_in`` with their groups' B and C, the
            # convolution over x, B and C, a decay rate, a skip and a step bias a head, the gated norm's weight a
            # channel and the held heads' rows of ``W_out``
            heads, inner, mixed = cfg.mamba_held[0], *_mamba_widths(cfg)
            layer["mamba"] = {"in_proj": (h, inner + mixed + heads), "kernel": (mixed, cfg.mamba_conv_kernel),
                              "conv_bias": (mixed,), "A_log": (heads,), "D": (heads,), "dt_bias": (heads,),
                              "norm": (inner,), "out": (inner, h)}
        else:
            # with an output gate a head's columns of ``q`` are [its query | its gate], twice the head size
            nh = cfg.heads_of(i)
            layer["attn"] = {"q": (h, nh * hd * (2 if cfg.attn_output_gate else 1)),
                             "k": (h, cfg.num_key_value_heads * hd),
                             "v": (h, cfg.num_key_value_heads * hd), "o": (nh * hd, h)}
            if cfg.qk_norm:
                layer["attn"].update(q_norm=(hd,), k_norm=(hd,))
            if cfg.attn_head_gate:  # one scalar a head and token: a projection of its own
                layer["attn"]["gate"] = (h, nh)
            if kind == "sparse_attention":  # the indexer's heads' queries, their ONE key head, a weight a head and token
                ni, di = cfg.indexer_num_heads, cfg.indexer_head_dim
                layer["indexer"] = {"q": (h, ni * di), "k": (h, di), "w": (h, ni)}
        ffn = lambda width, f: {"w1": (width, f), "w2": (f, width), **({"w3": (width, f)} if cfg.gated_experts else {})}
        if "ffn" not in halves:
            pass
        elif i < cfg.num_dense_layers:
            layer["dense"] = ffn(h, cfg.intermediate_size)
        else:
            # the experts work in the latent state where there is one, between ``latent_in`` and ``latent_out``
            e, f, width = cfg.n_held, cfg.moe_intermediate_size, cfg.moe_latent_size or h
            layer["moe"] = {"router": (h, cfg.num_experts),
                            **{name: (e,) + shape for name, shape in ffn(width, f).items()}}
            if cfg.moe_latent_size:
                layer["moe"].update(latent_in=(h, width), latent_out=(width, h))
            if cfg.n_shared_experts:
                layer["moe"]["shared"] = ffn(h, cfg.shared_expert_intermediate_size or cfg.n_shared_experts * f)
                if cfg.shared_expert_gate:
                    layer["moe"]["shared_gate"] = (h,)
        layers.append(layer)
    tree = {"embed": (cfg.vocab_size, h), "final_norm": (h,), "layers": layers}
    if not cfg.tie_word_embeddings:
        tree["head"] = (cfg.vocab_size, h)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _delta_widths(cfg: Lfm2MoeConfig) -> Tuple[int, int]:
    """Of a ``linear_attention`` layer: (the columns of q, which k has too; those of v, which z has too)."""
    return (cfg.linear_num_key_heads * cfg.linear_key_head_dim, cfg.linear_num_value_heads * cfg.linear_value_head_dim)


def _mamba_widths(cfg: Lfm2MoeConfig) -> Tuple[int, int]:
    """Of a ``mamba2`` layer's share: (the channels of x, which z has too: heads held x head size; those the
    convolution mixes: x and the held groups' B and C)."""
    heads, groups = cfg.mamba_held
    inner = heads * cfg.mamba_head_dim
    return inner, inner + 2 * groups * cfg.ssm_state_size


def training_bytes(cfg: Lfm2MoeConfig) -> Dict[str, int]:
    """Device bytes one individual's training takes, by arithmetic.

    ``state``: float32 weights, gradients and AdamW's two moments, 16 bytes a
    parameter, counted off :func:`param_shapes` -- so every attention layer at
    its own query heads, its head gates with it (a configuration whose layer
    types differ in their heads: 48 and 64 of 128 columns over 2,048 channels
    are 29.5 M and 37.9 M parameters a layer).  ``activations``: what a step keeps beside them under per-layer
    rematerialisation -- every layer's input and the larger of the two things
    that are never alive together: the float32 logits with their gradient (the
    head's backward pass, before any layer's), and the widest layer's interior
    (dense feed-forward; the expert rows' buffer at its worst-case height: the
    branch a program must have room for, whichever a call takes; a
    ``linear_attention`` layer's, at the widest point of its backward pass, the
    delta core's scan rule (:func:`_affine_scan`): what the forward pass keeps
    -- the in-projection in the compute dtype, the float32 q, k, v and gates of
    every value head, what each chunk wrote and its solved system ``[U | W]``,
    the system's and ``P``'s rows, ``K`` and ``exp(G) q - P W`` a position, the
    state that entered each chunk and ``A`` a head and chunk (``B`` and ``P U``
    are used up where they are made) -- and, beside it, the three stacked
    cotangents of that rule: what the outputs sent to each state, the ``dS`` it
    emits, which is ``dB``, and ``dA``; a ``mamba2`` layer's share: the
    in-projection in the compute dtype, the float32 x, B, C, step and gate of
    the heads held, a chunk's masked ``C B'`` rows a head, and the state that
    entered each chunk with its cotangent).  The row buffer's worst case is
    ``min(top-k, experts held) x tokens`` rows -- a token reaches a held expert
    once -- of the width the experts work in, and a layer that is a mixer alone
    or a feed-forward alone has one norm.  An estimate to decide a width by, not
    a measurement.
    """
    n_params = sum(math.prod(s) for s in jax.tree_util.tree_leaves(param_shapes(cfg), is_leaf=_is_shape))
    t, h = cfg.tokens_per_step, cfg.hidden_size
    per_row = 4 * (cfg.moe_latent_size or h) + (6 if cfg.gated_experts else 4) * cfg.moe_intermediate_size
    interior = max(6 * t * cfg.intermediate_size if cfg.num_dense_layers else 0,
                   min(cfg.num_experts_per_tok, cfg.n_held) * t * per_row) * 2
    if "linear_attention" in cfg.layer_types:
        keys, values = _delta_widths(cfg)
        nv, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
        in_proj = 2 * (2 * keys + 2 * values)  # a token, compute dtype
        operands = 4 * nv * (2 * dk + dv + 2)  # float32 q, k, v, g and beta of every value head
        systems = 4 * nv * (2 * (dk + dv) + 2 * cfg.delta_chunk)  # what a chunk wrote and its solution; the system's and q k' D's rows
        passes = 4 * nv * 2 * dk  # K and exp(G) q - P W: the batched products' operands that are no operand of the rule itself
        chunks = -(-t // cfg.delta_chunk)
        stacks = 4 * nv * dk * (dv + dk) * chunks  # the state that entered each chunk, and A
        sent = 4 * nv * dk * (2 * dv + dk) * chunks  # the cotangents of the entering states, of the states left (dB) and of A
        interior = max(interior, t * (in_proj + operands + systems + passes) + stacks + sent)
    if "mamba2" in cfg.layer_types:
        (heads, _), (inner, mixed) = cfg.mamba_held, _mamba_widths(cfg)
        in_proj = 2 * (inner + mixed)  # a token, compute dtype
        operands = 4 * (2 * inner + mixed + 2 * heads)  # float32 x, B and C, the gated output, the step and its decay
        rows = 4 * heads * cfg.mamba_chunk  # a position's row of a chunk's masked C B', a head
        states = 2 * 4 * heads * cfg.mamba_head_dim * cfg.ssm_state_size * -(-t // cfg.mamba_chunk)  # and their cotangents
        interior = max(interior, t * (in_proj + operands + rows) + states)
    if cfg.sparse_layers:
        # a block of queries against every key up to its last, float32: each query head's scores, their softmax and its
        # cotangent, the indexer's heads' products, its score, the mask and the mean share of the heads
        heads = max(cfg.heads_of(i) for i in cfg.sparse_layers)
        interior = max(interior, 4 * cfg.batch_sequences * min(cfg.attn_block, cfg.seq_len) * cfg.seq_len
                       * (3 * heads + cfg.indexer_num_heads + 3))
    activations = 2 * t * h * (len(cfg.layer_types) + 1) + max(interior, 2 * 4 * t * cfg.vocab_size)
    return {"params": n_params, "state": 16 * n_params, "activations": activations,
            "total": 16 * n_params + activations}


#: What a layer can be (``layer_types``): its operator, or ``routed`` -- a routed feed-forward alone, which makes
#: every other layer of the model its operator alone (:meth:`Lfm2MoeConfig.halves_of`).
LAYER_KINDS = ("conv", "linear_attention", "mamba2", "full_attention", "sliding_attention", "latent_attention",
               "sparse_attention", "routed")
#: Those of them that are attention over keys under a mask, whose causal core is :func:`_causal_core`'s.
ATTENTION_KINDS = ("full_attention", "sliding_attention", "latent_attention")
#: The programs the delta rule's core has, as the spans and the counter name them (one today).
LINEAR_CORE_PROGRAMS = ("chunked",)
#: The products a chunk step of :func:`_delta_core_xla`'s scan runs in sequence on the state, forward and backward
#: alike (the ``train`` spans' ``linear_core_chain_products``): everything else of the rule is batched over all chunks.
LINEAR_CORE_CHAIN_PRODUCTS = 1
#: The same of the fused kernels (:mod:`gentun_tpu.models.delta_kernel`), which hold no ``A`` and ``B``: ``W S``, then
#: ``K' (U - W S)`` forward; ``K dS``, then ``W' dV`` backward.
LINEAR_CORE_KERNEL_CHAIN_PRODUCTS = 2
#: The programs a ``mamba2`` layer's core has, as the spans and the counter name them (one: XLA's ops in chunks).
STATE_SPACE_CORE_PROGRAMS = ("chunked",)
#: The masks the core has, as the spans and the counter name them.
MASKS = ("causal", "window")
#: The programs a ``sparse_attention`` layer's core has, as the spans and the counter name them: XLA's query blocks
#: (:func:`_sparse_core`), and the fused kernels of :mod:`gentun_tpu.models.sparse_kernel` (:func:`_sparse_kernel_core`).
SPARSE_CORE_PROGRAMS = ("blockwise", "kernel")
#: The query blocks a group of the sparse core's table holds (:func:`_sparse_blocks`).
_SPARSE_GROUP = 4
#: What a ``sparse_attention`` layer keeps for its backward pass under rematerialisation, by name: the selection's choice,
#: as bits (the rematerialised forward does not select again), the core's output (134 MB a layer and sequence of
#: 16,384: with it kept, the rematerialised forward does not run the core again; XLA's blocks then run each block's
#: forward once more in the backward pass, on its own: two forward runs of the core a step, not three) and, where the
#: core is the fused kernels, their log-sum-exp (float32 a head and query: 2 MB a layer, 32 MB as the chip lays out an
#: array whose last axis is a key-value head's 8 query heads of 128 lanes; with ``out`` and it kept the forward kernel runs
#: once a step and the backward kernel starts from them).
SPARSE_KEPT = ("sparse_kept", "sparse_out", "sparse_lse")

#: A program of this family is one individual wide, always: the published cut
#: takes 10.4 of a chip's 16 GB in state alone, and a second width would be a
#: second compiled train program, which the family promises not to have.
PROGRAM_WIDTH = 1


def _require_fit(cfg: Lfm2MoeConfig) -> None:
    """Refuse, before anything compiles, a configuration whose one individual
    cannot fit the device (where the backend says how much memory it has)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    limit, need = stats.get("bytes_limit"), training_bytes(cfg)
    if limit and need["total"] > limit:
        raise ValueError(
            f"one individual needs ~{need['total'] / 1e9:.1f} GB ({need['params'] / 1e6:.0f} M parameters x 16 B "
            f"+ {need['activations'] / 1e9:.1f} GB of activations); the device has {limit / 1e9:.1f} GB: cut the "
            f"depth, the experts held or the tokens a step")


# -- the layers ---------------------------------------------------------------------------------


def _use_megablox() -> bool:
    return jax.default_backend() == "tpu"


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """megablox tiles for a product of ``m`` rows, contraction ``k`` and ``n``
    columns: the forward product, and each of the backward pass's two at its own
    sizes (the kernels look the tiles up by shape).  The row tile divides the
    buffer.  A size that is no whole number of tiles takes the widest tile of
    whole 128 lanes, up to four tiles, that divides it: 1408 = 11 x 128 whole,
    2304 = 4.5 x 512 as two of 1152; no tile runs ragged.  On the chip the three
    products forward and backward at width 1408 took 6.43 ms whole, 7.83 at a
    ragged 512 (8% of its columns empty), 11.8 at 128 (the rows read eleven
    times; 12,288 rows of a 33,792-row buffer; PERF.md, PR 32), and at 2304 x 896
    5.14 ms at 1152, 5.26 at 768, 5.69 at 384, 5.80 at a ragged 512, while 2304
    whole runs the weight-gradient kernel out of VMEM (16,384 rows of a
    45,056-row buffer; PERF.md, PR 34).  A size with no such tile is one tile,
    whole, up to four tiles, and ragged beyond."""
    def fit(size: int, tile: int) -> int:
        if size % tile == 0:
            return tile
        whole = size if size <= 4 * tile else tile
        return next((t for t in range(min(size, 4 * tile) // 128 * 128, 0, -128) if size % t == 0), whole)

    return math.gcd(m, _GMM_TILING[0]), fit(k, _GMM_TILING[1]), fit(n, _GMM_TILING[2])


def _grouped_matmul(rows, weights, group_sizes):
    """``rows`` (R, K) sorted by group against ``weights`` (G, K, N): group g's
    rows times ``weights[g]``.  Cost follows ``sum(group_sizes)``; the rows past
    it are undefined and the caller masks them."""
    if _use_megablox():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(rows, weights, group_sizes, preferred_element_type=rows.dtype, tiling=_gmm_tiling)
    return jax.lax.ragged_dot(rows, weights, group_sizes)


def _rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * weight


def _dot(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def _conv_op(p, x, cfg: Lfm2MoeConfig, dtype):
    """Gated short convolution on (sequences, length, hidden)."""
    gate_b, gate_c, u = jnp.split(_dot(x, p["in_proj"], dtype), 3, axis=-1)
    z = gate_b * u
    taps = cfg.conv_L_cache
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p["kernel"].astype(dtype)
    y = sum(kernel[:, j] * padded[:, j:j + z.shape[1]] for j in range(taps))
    return _dot(gate_c * y, p["out_proj"], dtype)


def yarn_inv_freq(dim: int, theta: float, scaling: Mapping[str, Any]) -> np.ndarray:
    """YaRN's ``dim / 2`` rotary frequencies (arXiv:2309.00071, as the deepseek_v2 modelling code
    blends them): below the correction dimension of ``beta_fast`` the plain frequency
    ``theta^(-2i/dim)``, above that of ``beta_slow`` the frequency divided by ``factor``, a linear
    ramp between.  A correction dimension is where a rotation count over the original context is
    met: ``dim * ln(original / (2 pi beta)) / (2 ln theta)``, rounded outwards."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    turn = lambda beta: dim * math.log(scaling["original_max_position_embeddings"] / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    low, high = max(math.floor(turn(scaling["beta_fast"])), 0), min(math.ceil(turn(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / scaling["factor"] * ramp + plain * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_amplitude(scaling: Mapping[str, Any]) -> float:
    """What YaRN multiplies cos and sin by (so a score carries its square), in either published
    form: an explicit ``attention_factor`` (Mellum2's ``rope_parameters``), or ``mscale`` over
    ``mscale_all_dim`` (DeepSeek-V2's ``rope_scaling``, which puts its ``m^2`` on the softmax
    scale: :func:`latent_softmax_scale`); with neither, ``0.1 ln factor + 1``."""
    if "attention_factor" in scaling:
        return float(scaling["attention_factor"])
    if "mscale" in scaling and "mscale_all_dim" in scaling:
        return yarn_mscale(scaling["factor"], scaling["mscale"]) / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    return yarn_mscale(scaling["factor"], 1.0)


def _rope_tables(x, theta, scaling: Optional[Mapping[str, Any]], rotary: Optional[int] = None, chunks: int = 0,
                 sections: Optional[Sequence[int]] = None, positions=None):
    """cos and sin of the positions of ``x`` (sequences, length, ..., head size), float32, one
    column a rotated pair (half the head size, or half of ``rotary``, the leading columns that
    turn) and shaped to broadcast against ``x``'s halves.
    With ``scaling`` (YaRN) the frequencies are :func:`yarn_inv_freq`'s and both carry
    :func:`yarn_amplitude`.  ``chunks``: ``x`` holds its sequences in that many chunks each,
    (sequences x chunks, chunk, ..., head size), and row ``i`` is at chunk ``i % chunks``.
    ``positions`` (streams, length): where each token stands, one row a position stream (None: its
    index in the sequence, in every stream); ``sections``: how many of the rotated pairs, in
    order, take their angle from each stream (``mrope_section``: the first 16 of 64 from the
    temporal stream, then 24 and 24 from the two spatial ones; None: all from the first)."""
    turning = x.shape[-1] if rotary is None else rotary
    half = turning // 2
    if scaling is None:
        inv_freq, amplitude = theta ** (-jnp.arange(half, dtype=jnp.float32) / half), 1.0
    else:
        inv_freq, amplitude = jnp.asarray(yarn_inv_freq(turning, theta, scaling)), yarn_amplitude(scaling)
    rows = max(chunks, 1)  # of the tables: a sequence's chunks, which every sequence shares
    if sections is None and positions is None:
        angle = jnp.arange(rows * x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    else:  # a pair's angle is its own stream's position times its frequency
        streams = jnp.arange(rows * x.shape[1], dtype=jnp.float32)[None, :] if positions is None \
            else jnp.asarray(positions, jnp.float32).reshape(-1, rows * x.shape[1])
        stream_of = np.repeat(np.arange(len(sections)), sections) if sections is not None else np.zeros(half, np.int64)
        angle = streams[np.minimum(stream_of, streams.shape[0] - 1)].T * inv_freq[None, :]
    per_position = (rows, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angle).reshape(per_position), jnp.sin(angle).reshape(per_position)
    if chunks:
        cos, sin = (jnp.tile(table, (x.shape[0] // chunks,) + (1,) * (x.ndim - 1)) for table in (cos, sin))
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    return cos, sin


def _rope(x, theta, scaling: Optional[Mapping[str, Any]] = None, positions=None):
    """Rotary embedding, rotate-half layout, on (sequences, length, ..., head size):
    heads, or key-value heads and their query heads, between; float32.  The two
    halves are sliced and concatenated: the form for a few columns (latent
    attention's 64 rope columns a head, concatenated to the rest anyway; an indexer's 64,
    at ``positions``' first stream)."""
    cos, sin = _rope_tables(x, theta, scaling, positions=positions)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rope_whole_heads(x, theta, scaling: Optional[Mapping[str, Any]] = None, rotary: Optional[int] = None,
                      chunks: int = 0, sections: Optional[Sequence[int]] = None, positions=None):
    """:func:`_rope`'s function on whole heads without a slice or a concatenation:
    ``x * [cos | cos] + (x @ T) * [sin | sin]``, where ``T`` is the signed
    permutation that sends ``[x1 | x2]`` to ``[-x2 | x1]``.  Every product with
    ``T`` has one term, so the float32 result and its cotangent are
    :func:`_rope`'s to the last bit on the chip (HIGHEST: a float32 ``x``, the
    normed q and k and every cotangent, crosses the MXU in pieces that add up to
    it exactly).  Why: XLA:TPU writes a concatenation as a pass of its own over
    float32 halves, and writes this form as ONE fusion an operand, the product
    with ``T`` at its heart and the norm, cos and sin, the core's scale and the
    cast to the compute dtype around it, forward and backward (PERF.md, PR 36:
    8.56 GB a layer-step outside products and kernels at Mellum2's shape
    against 3.56; 45.5 ms against 35.6).  With ``rotary`` under the head size
    only the leading ``rotary`` columns turn (pairs ``(c, c + rotary / 2)``
    inside them): the tables read cos 1 and sin 0 on the columns that pass, and
    ``T`` has no entry for them -- the same one fusion.  ``chunks``: ``x`` holds
    each sequence in that many chunks, as the banded core's queries are
    (:func:`_rope_tables`); ``sections`` and ``positions``: rope by sections over
    several position streams, the tables' alone."""
    size = x.shape[-1]
    turning = size if rotary is None else rotary
    cos, sin = (jnp.concatenate([table, table], axis=-1)
                for table in _rope_tables(x, theta, scaling, rotary, chunks, sections, positions))
    if turning < size:
        passing = [(0, 0)] * (cos.ndim - 1) + [(0, size - turning)]
        cos, sin = jnp.pad(cos, passing, constant_values=1.0), jnp.pad(sin, passing)
    half = turning // 2
    turn = np.zeros((size, size), np.float32)
    turn[np.arange(half) + half, np.arange(half)] = -1.0  # column j < half takes -x[half + j]
    turn[np.arange(half), np.arange(half) + half] = 1.0  # column half + j takes x[j]
    turned = jnp.einsum("...d,de->...e", x, jnp.asarray(turn, x.dtype), preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
    return x * cos + turned * sin


def _kernel_blocks(length: int, columns: int = 0) -> Optional[Dict[str, int]]:
    """The fused kernel's blocks at this length, none larger than it, or None
    where the length is not a whole number of each (of 128 lanes at least): the
    kernel has no ragged last block.  ``columns``: those of a head of q and of v
    together; past ``_ATTN_KERNEL_COLUMNS`` the backward kernel's query block is
    half as tall -- a rule by shape, as :func:`_gmm_tiling`'s."""
    blocks = dict(_ATTN_KERNEL_BLOCKS)
    if columns > _ATTN_KERNEL_COLUMNS:
        blocks["block_q_dkv"] //= 2
    blocks = {name: min(size, length) for name, size in blocks.items()}
    return blocks if all(length % size == 0 and size % 128 == 0 for size in blocks.values()) else None


def _core_columns(qk: int, v: int) -> int:
    """The columns the fused core holds a head: q's (k's the same; over 128 a whole number of 128 lanes,
    :func:`_kernel_core` pads them) and v's."""
    return (qk + (-qk % 128 if qk > 128 else 0)) + v


def _use_attention_kernel(length: int) -> bool:
    """Whether the causal core of a program traced now runs as the fused
    kernel: the backend is a TPU, the length is a whole number of the kernel's
    blocks and this jax ships the kernel."""
    if jax.default_backend() != "tpu" or _kernel_blocks(length) is None:
        return False
    try:
        from jax.experimental.pallas.ops.tpu import splash_attention  # noqa: F401
    except ImportError:
        return False
    return True


def _kernel_chunk(length: int, window: Optional[int], group: int, columns: int = 0) -> int:
    """The positions a chunk of the banded core holds (:func:`_kernel_core`), or 0
    where the core runs unbanded: no window, or no chunk of ``_ATTN_KERNEL_CHUNKS``
    that divides the window and the length, whose ``group`` query heads' rows are
    whole query blocks and whose keys (chunk + window) are whole 128 lanes -- a
    rule by shape, as :func:`_gmm_tiling`'s."""
    if window is None or window >= length:
        return 0
    return next((chunk for chunk in _ATTN_KERNEL_CHUNKS
                 if window % chunk == 0 and length % chunk == 0 and (chunk + window) % 128 == 0
                 and _kernel_blocks(group * chunk, columns) is not None), 0)


def _band_blocks(window: int, chunk: int, group: int, columns: int = 0) -> Dict[str, int]:
    """The banded kernel's blocks: the query block of :func:`_kernel_blocks` (a
    chunk's rows are a whole number of it), ONE key block of the chunk's keys, and
    inside it the widest product of whole 128 lanes that divides them up to the
    unbanded kernel's."""
    blocks, keys = _kernel_blocks(group * chunk, columns), chunk + window
    compute = next(n for n in range(min(_ATTN_BAND_COMPUTE, keys) // 128 * 128, 0, -128) if keys % n == 0)
    return dict(blocks, block_kv=keys, block_kv_compute=compute, block_kv_dkv=keys, block_kv_dkv_compute=compute)


def _band_segments(length: int, window: int, chunk: int) -> np.ndarray:
    """(chunks, chunk + window) int32: 1 where a chunk's key stands at a position
    of the sequence, 0 where it stands before position 0 (the first ``window /
    chunk`` chunks' leading keys).  Every query's segment is 1, so the kernel
    gives such a key no weight at all."""
    first = np.arange(0, length, chunk)[:, None] - window  # the position of each chunk's first key
    return (first + np.arange(chunk + window)[None, :] >= 0).astype(np.int32)


def _splash_kernel(length: int, group: int, window: Optional[int], columns: int = 0):
    """The fused kernel of one key-value head and its ``group`` query heads over
    ``length`` positions.  The mask is an object of the library: ``CausalMask``,
    or, with ``window``, ``LocalMask`` reaching ``window - 1`` keys back and none
    ahead (a query's own position counts into the window).  The library turns it
    into a table of the (query block, key block) pairs that hold a visible key
    and runs its grid over those alone, forward and backward.

    Where :func:`_kernel_chunk` gives a chunk, the kernel is that of ONE chunk
    of the banded core: ``group x chunk`` rows (head g's query c at row ``g chunk
    + c``) against the ``chunk + window`` keys that end with the chunk's last.
    The mask is still the library's ``LocalMask``, computed inside the kernel from
    the rows' positions: row r stands at ``window + r % chunk`` of the chunk's
    keys, which is all the object is told (``q_sequence``)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

    chunk = _kernel_chunk(length, window, group, columns)
    if chunk:
        mask = masks.LocalMask((group * chunk, chunk + window), window_size=(window - 1, 0), offset=0)
        mask.q_sequence = (window + np.arange(group * chunk) % chunk).astype(np.int32)
        return splash.make_splash_mqa_single_device(
            masks.MultiHeadMask([mask]),
            block_sizes=splash.BlockSizes(**_band_blocks(window, chunk, group, columns), use_fused_bwd_kernel=True))
    shape = (length, length)
    mask = masks.CausalMask(shape) if window is None else masks.LocalMask(shape, window_size=(window - 1, 0), offset=0)
    return splash.make_splash_mqa_single_device(
        masks.MultiHeadMask([mask] * group),
        block_sizes=splash.BlockSizes(**_kernel_blocks(length, columns), use_fused_bwd_kernel=True))


@functools.lru_cache(maxsize=16)
def _kernel_visits(length: int, window: Optional[int], columns: int = 0, group: int = 1) -> Dict[str, int]:
    """What the fused kernel visits for one head and sequence, read from the
    kernel's own table (:func:`_splash_kernel`), never from a formula beside it:
    the (query block, key block) ``pairs`` and their area ``elements`` of the
    forward kernel, ``pairs_bwd`` and ``elements_bwd`` of the backward one; under
    a window also ``chunk``, the banded core's, 0 where the core runs unbanded.
    A banded kernel's table is one chunk's, of ``group`` heads together: a head's
    share of all chunks is counted (its grid steps rounded up to whole).  What a
    roofline's count of the executed work reads (the ``train`` span carries it)."""
    chunk = _kernel_chunk(length, window, group, columns)
    with jax.ensure_compile_time_eval():
        kernel = _splash_kernel(length, group if chunk else 1, window, columns)
    blocks = _band_blocks(window, chunk, group, columns) if chunk else _kernel_blocks(length, columns)
    calls, heads = (length // chunk, group) if chunk else (1, 1)
    visited = lambda info: int(np.count_nonzero(np.asarray(info.block_mask)[0])) * calls  # one table serves every head
    forward, backward = visited(kernel.fwd_mask_info), visited(kernel.dkv_mask_info)
    return {"pairs": -(-forward // heads), "elements": forward * blocks["block_q"] * blocks["block_kv"] // heads,
            "pairs_bwd": -(-backward // heads),
            "elements_bwd": backward * blocks["block_q_dkv"] * blocks["block_kv_dkv"] // heads,
            **({} if window is None else {"chunk": chunk})}


def _chunk_rows(a):
    """(sequences x chunks, chunk, kv heads, group, size) as the banded kernel holds it: (sequences x chunks, kv
    heads, 1, group x chunk, size), head g's position c of a chunk at row ``g chunk + c``.  No pass over memory
    where ``a`` was written head-major a chunk (:func:`_head_major`)."""
    return a.transpose(0, 2, 3, 1, 4).reshape(a.shape[0], a.shape[2], 1, -1, a.shape[-1])


def _from_chunk_rows(a, group: int):
    """:func:`_chunk_rows` undone."""
    return a.reshape(a.shape[0], a.shape[1], group, a.shape[3] // group, a.shape[-1]).transpose(0, 3, 1, 2, 4)


def _banded_core(kernel, q, k, v, window: int, chunks: int):
    """The windowed core in chunks, ``chunks`` a sequence: ``q`` (sequences x
    chunks, chunk, kv heads, group, head size), a chunk a row; ``k`` and ``v``
    (sequences x chunks, chunk, kv heads, head size) likewise; ``kernel`` one
    chunk's (:func:`_splash_kernel`).  A chunk's queries of all ``group`` heads
    are one block of rows, its keys the ``chunk + window`` positions that end
    with its last: its own and the ``window / chunk`` chunks before it of the
    same sequence, zero rows where those would lie before position 0, which are
    shut out by their segment (:func:`_band_segments`), never scored.  The
    result as ``q``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    rows, chunk, nkv, group, _ = q.shape
    s, reach = rows // chunks, window // chunk

    def keys_of(a):  # (sequences x chunks, kv heads, chunk + window, head size): chunk i holds chunks i - reach .. i
        held = jnp.pad(a.reshape(s, chunks, *a.shape[1:]), ((0, 0), (reach, 0)) + ((0, 0),) * (a.ndim - 1))
        held = jnp.concatenate([held[:, j:j + chunks] for j in range(reach + 1)], axis=2)
        return held.reshape(rows, chunk + window, nkv, -1).transpose(0, 2, 1, 3)

    segments = splash.SegmentIds(q=jnp.ones((group * chunk,), jnp.int32),
                                 kv=jnp.asarray(np.tile(_band_segments(chunks * chunk, window, chunk), (s, 1))))
    one_chunk = jax.vmap(kernel, in_axes=(0, 0, 0, None))  # over the key-value heads
    out = jax.vmap(one_chunk, in_axes=(0, 0, 0, splash.SegmentIds(q=None, kv=0)))(_chunk_rows(q), keys_of(k), keys_of(v), segments)
    return _from_chunk_rows(out, group)


def _kernel_core(q, k, v, scale: float, window: Optional[int] = None):
    """The causal core as one fused kernel with its own backward: scores, the
    running maximum, sum and accumulator in float32 on the chip's fast memory,
    the output and the log-sum-exp kept for the backward pass, never a score.
    ``q`` (sequences, length, kv heads, queries a kv head, head size), float32
    or the compute dtype; ``k`` (sequences, length, kv heads, head size) and
    ``v`` (the same, at a head size of its own) in the compute dtype.  Those are
    shapes, not copies: the kernel reads head-major arrays (sequences, kv
    heads, ..., length, head size), and an operand built from head-major parts
    (:func:`_head_major`) reaches it without a transposing pass.  ``scale``
    multiplies the scores and goes onto ``q`` in float32 before its one cast
    (exact where it is a power of two: a head size of 64).  A head size of q and
    k over 128 that is no whole number of 128 lanes (latent attention's 192) gets
    zero columns, which is exact, and gets them FIRST: XLA writes a padding of a
    concatenation as one pass over the parts, the scale and the cast in it,
    where a padding of a scaled array is a pass of its own (PERF.md, PR 33).  The
    kernel takes one key-value head with its query heads (no copy of K or V);
    ``vmap`` makes the key-value heads and the sequences its outer grid.

    With ``window`` a query sees that many keys, its own the last
    (:func:`_splash_kernel`), and where the shape allows (:func:`_kernel_chunk`)
    the core runs banded (:func:`_banded_core`): the unbanded kernel costs every
    (query block, key block) pair it visits its whole area, most of which a
    window hides, and each of a key-value head's query heads walks the same keys
    on a grid of its own.  The banded core's operands and result are a chunk a
    row, (sequences x chunks, chunk, ...): the same shapes as whole sequences to
    jax, and the same memory where the caller wrote each chunk head-major as a
    sequence would be (:func:`_attention` does: XLA cancels the reshapes between
    there and here); for operands written head-major a whole sequence, cutting
    them is a transposing pass over each, over the output and over every
    cotangent."""
    length, group = q.shape[1], q.shape[3]
    columns = _core_columns(q.shape[-1], v.shape[-1])
    pad = -q.shape[-1] % 128 if q.shape[-1] > 128 else 0
    if pad:  # zero columns add nothing to a score: 192 as 256 took 13.9 ms against 15.4 (PERF.md, PR 32)
        q, k = (jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) for a in (q, k))
    q = (q.astype(jnp.float32) * scale).astype(k.dtype)
    kernel = _splash_kernel(length, group, window, columns)
    chunk = _kernel_chunk(length, window, group, columns)
    if chunk:
        shape = q.shape[:-1] + v.shape[-1:]
        q, k, v = (a.reshape(-1, chunk, *a.shape[2:]) for a in (q, k, v))
        return _banded_core(kernel, q, k, v, window, length // chunk).reshape(shape)
    out = jax.vmap(jax.vmap(kernel))(q.transpose(0, 2, 3, 1, 4), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return out.transpose(0, 3, 1, 2, 4)


def _blockwise_core(q, k, v, scale: float, block: int, window: Optional[int] = None):
    """The causal core in query blocks of at most ``block`` as XLA programs: no
    (length x length) score array per head is alive, a block's scores are.
    Arguments as :func:`_kernel_core`'s.  With ``window`` a block is handed the
    keys from the last whole block that its first query still sees, and the mask
    hides what lies ``window`` or more positions back: the same function as the
    kernel's, at a cost that follows the window too."""
    length, dtype = q.shape[1], k.dtype
    block = min(block, length)
    if length % block:
        raise ValueError(f"seq_len {length} is not a multiple of attn_block {block}")
    q = q.astype(dtype)

    @jax.checkpoint
    def one_block(qb, kb, vb, first, first_key):
        scores = jnp.einsum("sqngd,sknd->sngqk", qb, kb, preferred_element_type=jnp.float32) * scale
        back = (first + jnp.arange(qb.shape[1]))[:, None] - (first_key + jnp.arange(kb.shape[1]))[None, :]
        seen = back >= 0 if window is None else (back >= 0) & (back < window)
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1).astype(dtype)
        return jnp.einsum("sngqk,sknd->sqngd", prob, vb)

    out = []
    for i in range(0, length, block):
        lo = 0 if window is None else max(0, (i - window + 1) // block * block)
        out.append(one_block(q[:, i:i + block], k[:, lo:i + block], v[:, lo:i + block], i, lo))
    return jnp.concatenate(out, axis=1)


def _causal_core(q, k, v, scale: float, cfg: Lfm2MoeConfig, window: Optional[int] = None):
    """The causal core (scores, softmax, values) of every attention operator:
    the fused kernel where :func:`_use_attention_kernel` says so and XLA's query
    blocks of ``attn_block`` elsewhere: one function, chosen by backend and shape.
    ``window``: the keys a query sees, its own position the last of them (a
    ``sliding_attention`` layer); None: every key up to its own."""
    if _use_attention_kernel(q.shape[1]):
        return _kernel_core(q, k, v, scale, window)
    return _blockwise_core(q, k, v, scale, cfg.attn_block, window)


def _attention(p, x, cfg: Lfm2MoeConfig, dtype, kind: str = "full_attention"):
    """Causal GQA on (sequences, length, hidden); the core is :func:`_causal_core`'s.
    The layer's type ``kind`` decides its mask (:meth:`Lfm2MoeConfig.window_of`),
    its rope (:meth:`Lfm2MoeConfig.rope_of`) and the columns of a head that rope
    turns (:meth:`Lfm2MoeConfig.rotary_of`); the layer's query heads are what
    its output projection holds (``o``: heads x head size rows, as
    :func:`param_shapes` gave it from :meth:`Lfm2MoeConfig.heads_of`), each
    key-value head serving ``heads / kv heads`` of them; where the
    configuration tells attention layers apart by type, ``proj``, ``rope``,
    ``core`` and ``gate`` are scopes.

    Each operand of the fused core is written once, in the order the kernel
    reads and in the compute dtype: the q, k and v products emit head-major
    (:func:`_head_major`, the key-value heads and their query heads in the
    weights' shape, so no reshape follows); the q/k norm, rope
    (:func:`_rope_whole_heads`), the core's scale and the one cast are float32
    arithmetic inside one fusion an operand; the output product contracts the
    kernel's head-major output as it is.  Where the windowed core runs banded
    (:func:`_kernel_chunk`) "a sequence" above reads "a chunk": the products
    take the tokens as (sequences x chunks, chunk, hidden), so q, k, v, the
    gates and the core's output are head-major a chunk, which is the order the
    banded kernel reads and writes (:func:`_banded_core`), and rope turns each
    chunk at its own positions."""
    hidden, window = x.shape[-1], cfg.window_of(kind)
    nkv, hd = cfg.num_key_value_heads, cfg.head_dim
    nh = p["o"].shape[0] // hd  # a matter of the layer: its parameters say
    part = jax.named_scope if cfg.typed_attention else (lambda name: contextlib.nullcontext())
    (theta, scaling), rotary = cfg.rope_of(kind), cfg.rotary_of(kind)
    # where the core runs banded, every operand of it, the gates and its output are made, read and written in the core's
    # chunks, each a sequence of its own to the products: (sequences x chunks, chunk, hidden) is the same memory
    chunk = _kernel_chunk(x.shape[1], window, nh // nkv, _core_columns(hd, hd)) if _use_attention_kernel(x.shape[1]) else 0
    chunks = x.shape[1] // chunk if chunk else 0
    rows = x.reshape(-1, chunk, hidden) if chunk else x
    with part("proj"):
        if cfg.attn_output_gate:  # a head's columns are [query | gate]: two products, as the latent operator's blocks
            w_q = p["q"].astype(dtype).reshape(hidden, nkv, nh // nkv, 2 * hd)
            q, gate = _head_major(rows, w_q[..., :hd]), _head_major(rows, w_q[..., hd:])
        else:
            q = _head_major(rows, p["q"].astype(dtype).reshape(hidden, nkv, nh // nkv, hd))
        k = _head_major(rows, p["k"].astype(dtype).reshape(hidden, nkv, hd))
        v = _head_major(rows, p["v"].astype(dtype).reshape(hidden, nkv, hd))
    with part("rope"):
        normed = lambda a, weight: _rms_norm(a, p[weight], cfg.norm_eps) if cfg.qk_norm else a
        if cfg.positional_encoding == "none":  # the order of the tokens reaches such a model through its other layers
            q, k = normed(q, "q_norm"), normed(k, "k_norm").astype(dtype)
        else:
            q = _rope_whole_heads(normed(q, "q_norm"), theta, scaling, rotary, chunks)
            k = _rope_whole_heads(normed(k, "k_norm"), theta, scaling, rotary, chunks).astype(dtype)
    with part("core"):
        if chunk:  # whole sequences to the core, which cuts them into these chunks again: shapes, not passes over memory
            q, k, v = (a.reshape(*x.shape[:2], *a.shape[2:]) for a in (q, k, v))
        out = _causal_core(q, k, v, 1.0 / math.sqrt(hd), cfg, window)
        if chunk:
            out = out.reshape(-1, chunk, *out.shape[2:])
    if cfg.attn_output_gate or cfg.attn_head_gate:
        with part("gate"):
            if cfg.attn_head_gate:  # one scalar a head and token, a float32 product as the router's; head-major as the core's output
                gate = jnp.einsum("slh,hng->sngl", rows.astype(jnp.float32), p["gate"].reshape(hidden, nkv, nh // nkv),
                                  precision=jax.lax.Precision.HIGHEST)
                gate = jnp.moveaxis(gate, -1, 1)[..., None]
            if chunk:  # in the rows the banded kernel wrote: XLA then reads its output once for the gate and for the kernel's own backward
                out, gate = _chunk_rows(out), _chunk_rows(gate)
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
            if chunk:
                out = _from_chunk_rows(out, nh // nkv)
    with part("proj"):
        out = jnp.einsum("slngd,ngdh->slh", out, p["o"].astype(dtype).reshape(nkv, nh // nkv, hd, hidden))
        return out.reshape(x.shape) if chunk else out


def _kth_largest(scores, k: int):
    """The ``k``-th largest of each row of ``scores`` (..., n), float32, exact: by bisection on
    the bit pattern.  A float32's order is an unsigned integer's once the sign bit is set on
    what is not negative and every bit flipped on what is; the largest integer that ``k`` of a
    row's keys reach is then built a bit at a time from the top, each bit one counting pass
    over the row: 32 passes, no sort (``lax.top_k`` of 2,048 over 16,384 is a sort on a TPU).
    A row with fewer than ``k`` entries gives a NaN, which its caller never reads."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def narrow(i, found):
        candidate = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= candidate[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, found)

    found = jax.lax.fori_loop(0, 32, narrow, jnp.zeros(scores.shape[:-1], jnp.uint32))
    return jax.lax.bitcast_convert_type(jnp.where(found >> 31 == 1, found & jnp.uint32(0x7FFFFFFF), ~found), jnp.float32)


def _indexer_scores(q_idx, k_idx, w_idx, first: int):
    """The indexer's score of every (query, key) of a block: ``sum_j w[t, j] relu(qI_j[t] . kI[s])``,
    minus infinity where the key lies ahead of the query (the block's first query stands at
    ``first``, its keys from position 0).  ``q_idx`` (sequences, queries, indexer heads, size)
    and ``k_idx`` (sequences, keys, size) in the compute dtype, their products accumulated in
    float32; ``w_idx`` (sequences, queries, indexer heads) float32, the score's two scales on it;
    relu, weights and the sum over the heads float32 arithmetic, no product (a float32
    contraction would cross a TPU's matrix unit in bfloat16).  Returns (sequences, queries,
    keys) float32."""
    dots = jnp.einsum("sqjd,skd->sqjk", q_idx, k_idx, preferred_element_type=jnp.float32)
    ahead = (first + jnp.arange(q_idx.shape[1]))[:, None] < jnp.arange(k_idx.shape[1])[None, :]
    return jnp.where(ahead, -jnp.inf, jnp.sum(jax.nn.relu(dots) * w_idx[..., None], axis=2))


def _sparse_blocks(length: int, block: int) -> Tuple[Tuple[int, int], ...]:
    """The sparse core's table: (a group of query blocks' first position, its end), in order.  A
    group is up to ``_SPARSE_GROUP`` blocks of ``block`` queries, each handed every key up to
    the GROUP's last query: one body a group in the program (a ``lax.map`` over its blocks), a
    quarter of the bodies a table of single blocks would make and a tenth more pairs.  What the
    core walks and what its count of visits reads."""
    block = min(block, length)
    if length % block:
        raise ValueError(f"seq_len {length} is not a multiple of attn_block {block}")
    reach = _SPARSE_GROUP * block
    return tuple((first, min(first + reach, length)) for first in range(0, length, reach))


def _sparse_visits(length: int, block: int) -> Dict[str, int]:
    """What the sparse core visits for one head and sequence, off its own table
    (:func:`_sparse_blocks`): the (query block, keys) ``pairs`` and the score ``elements`` in them."""
    block = min(block, length)
    table = _sparse_blocks(length, block)
    return {"pairs": sum((last - first) // block for first, last in table),
            "elements": sum((last - first) * last for first, last in table)}


def _by_block(body, first: int, last: int, block: int, *rows):
    """``body(*a block's rows, the block's first position)`` for each block of ``block`` queries
    of [first, last), stacked: ``rows`` are (sequences, length, ...) arrays cut to the group and
    handed over a block at a time; one trace of ``body`` (a ``lax.map``) where the group has
    more than one block."""
    n = (last - first) // block
    if n == 1:
        return jax.tree_util.tree_map(lambda a: a[None], body(*(a[:, first:last] for a in rows), first))
    split = lambda a: jnp.moveaxis(a[:, first:last].reshape(a.shape[0], n, block, *a.shape[2:]), 1, 0)
    return jax.lax.map(lambda xs: body(*xs[:-1], xs[-1]), (*map(split, rows), first + block * jnp.arange(n)))


def _kept(index, tau):
    """The mask that is data: a block's (query, key) pairs with ``I[t, s] >= tau[t]``, no key
    ahead of its query (:func:`_indexer_scores` scored those minus infinity)."""
    return (index >= tau[..., None]) & (index > -jnp.inf)


def _use_sparse_kernel(length: int, group: int, size: int, block: int) -> bool:
    """Whether the masked core of a ``sparse_attention`` layer of a program traced now runs as the fused kernels
    (:mod:`gentun_tpu.models.sparse_kernel`): the backend is a TPU, a head of ``size`` is whole 128 lanes, the length is
    whole super-tiles of the bits and whole tiles of the kernels, the loss pass's query block of ``block`` and the keys
    its groups reach are whole tiles too, and a key-value head's dk and dv fit fast memory
    (:func:`gentun_tpu.models.sparse_kernel.fits`).  A rule by backend and shape, as :func:`_use_attention_kernel`'s."""
    if jax.default_backend() != "tpu":
        return False
    from . import sparse_kernel
    block = min(block, length)
    return sparse_kernel.fits(length, group, size, block, _SPARSE_GROUP * block, _SPARSE_KERNEL_TILE)


def _sparse_kernel_dims(scale: float):
    """The static sizes the masked core's kernels are called with: their tile (``_SPARSE_KERNEL_TILE``), the scores'
    ``scale`` and the names their forward's residuals are kept under (``SPARSE_KEPT``'s second and third)."""
    from . import sparse_kernel
    return sparse_kernel.Dims(_SPARSE_KERNEL_TILE, scale, SPARSE_KEPT[1:])


def _packed(kept):
    """A mask (..., keys) as bits: int32 words (..., ceil(keys / 4,096) x 128), bit ``b`` of word ``l`` of a super-tile
    of 4,096 keys its key ``128 b + l`` (:func:`gentun_tpu.models.sparse_kernel.packed`: the layout is the kernels')."""
    from . import sparse_kernel
    return sparse_kernel.packed(kept)


def _unpacked(bits, keys: int):
    """:func:`_packed` undone for the first ``keys`` keys: bool (..., keys)."""
    from . import sparse_kernel
    return sparse_kernel.unpacked(bits, keys)


def _sparse_selection(q_idx, k_idx, w_idx, top: int, block: int):
    """Which keys each query keeps, as bits (:func:`_packed`): int32 (sequences, length, 128 a
    super-tile of 4,096 keys: length / 32 at whole super-tiles).  ``tau[t]`` is the ``top``-th largest indexer score among the keys up to
    ``t`` (:func:`_kth_largest`), minus infinity where ``t`` has no more than ``top`` of them (it
    then keeps them all); query ``t`` keeps key ``s <= t`` iff ``I[t, s] >= tau[t]``
    (:func:`_kept`) -- decided HERE, on the scores the threshold was found in, and handed on as
    the choice itself: a score computed again elsewhere may differ in its last bit, and a
    comparison with a threshold would then drop the very key that set it.  A query block at a
    time against the keys up to its group's last (:func:`_sparse_blocks`); no gradient passes (a
    choice has none).  33.5 MB a layer and sequence of 16,384: the first of what a
    ``sparse_attention`` layer keeps for its backward pass under rematerialisation (``SPARSE_KEPT``)."""
    length, block = q_idx.shape[1], min(block, q_idx.shape[1])
    q_idx, k_idx, w_idx = map(jax.lax.stop_gradient, (q_idx, k_idx, w_idx))
    from . import sparse_kernel
    width = sparse_kernel.words(length)

    def one_block(qib, wb, first, kib):
        with jax.named_scope("indexer_scores"):
            index = _indexer_scores(qib, kib, wb, first)
        with jax.named_scope("select"):
            if kib.shape[1] <= top:  # none of the group's queries has more keys than it may keep
                tau = jnp.full(index.shape[:2], -jnp.inf, jnp.float32)
            else:
                tau = jnp.where(first + jnp.arange(block) + 1 > top, _kth_largest(index, top), -jnp.inf)
            return _packed(_kept(index, tau))

    chosen = []
    for first, last in _sparse_blocks(length, block):
        bits = _by_block(functools.partial(one_block, kib=k_idx[:, :last]), first, last, block, q_idx, w_idx)
        bits = jnp.moveaxis(bits, 0, 1).reshape(q_idx.shape[0], last - first, -1)
        chosen.append(jnp.pad(bits, ((0, 0), (0, 0), (0, width - bits.shape[-1]))))
    return jnp.concatenate(chosen, axis=1)


def _heads_share(prob):
    """``p``: a kept key's share in the heads' attention, their mean, a constant to the gradient;
    ``prob`` (sequences, kv heads, group, queries, keys)."""
    return jax.lax.stop_gradient(jnp.mean(prob, axis=(1, 2)))


def _indexer_loss(index, share, kept):
    """``sum_s p log(p / softmax_kept(I[t, :]))`` over a block's queries, a sequence
    (sequences,): the gradient reaches ``index`` alone."""
    log_index = jax.nn.log_softmax(jnp.where(kept, index, -jnp.inf), axis=-1)
    terms = jax.scipy.special.xlogy(share, share) - share * jnp.where(kept, log_index, 0.0)
    return jnp.sum(jnp.where(kept, terms, 0.0), axis=(1, 2))


def _sparse_core(q, k, v, q_idx, k_idx, w_idx, chosen, scale: float, block: int):
    """Attention over the keys the indexer chose, and the indexer's loss, in query blocks of at
    most ``block`` as XLA programs: no (length x length) array is alive, a block's scores against
    the keys up to its group's last query are (:func:`_sparse_blocks`).  ``q``, ``k``, ``v`` as
    :func:`_blockwise_core`'s; ``q_idx``, ``k_idx``, ``w_idx`` as :func:`_indexer_scores`'s over
    the whole sequence; ``chosen`` the selection's bits (:func:`_sparse_selection`).

    A block unpacks its queries' choices -- a mask that is data -- and runs every head's softmax
    over the kept keys alone; it computes its indexer scores ``I`` again for the loss (an eighth
    of the core's products: cheaper than keeping them).  The loss: ``p`` the heads'
    mean share of each kept key, a constant to the gradient (:func:`_heads_share`);
    ``sum_s p log(p / softmax_kept(I[t, :]))`` a query (:func:`_indexer_loss`), whose gradient
    reaches ``I`` alone (so the indexer's operands, and nothing of the trunk).  Returns (the
    heads' outputs as ``q``, the queries' loss terms summed a sequence (sequences,) float32, the
    (query, key) pairs kept, int32)."""
    length, dtype = q.shape[1], k.dtype
    q = q.astype(dtype)

    @jax.checkpoint
    def one_block(qb, kb, vb, qib, kib, wb, bits, first):
        with jax.named_scope("indexer_scores"):
            index = _indexer_scores(qib, kib, wb, first)
        with jax.named_scope("core"):
            kept = _unpacked(bits, kb.shape[1])
            scores = jnp.einsum("sqngd,sknd->sngqk", qb, kb, preferred_element_type=jnp.float32) * scale
            prob = jax.nn.softmax(jnp.where(kept[:, None, None], scores, -jnp.inf), axis=-1)
            out = jnp.einsum("sngqk,sknd->sqngd", prob.astype(dtype), vb)
        with jax.named_scope("indexer_loss"):
            loss = _indexer_loss(index, _heads_share(prob), kept)
        return out, loss, jnp.sum(kept, dtype=jnp.int32)

    out, loss, pairs = [], 0.0, 0
    block = min(block, length)
    for first, last in _sparse_blocks(length, block):
        body = lambda qb, qib, wb, bits, at, last=last: one_block(qb, k[:, :last], v[:, :last], qib, k_idx[:, :last], wb, bits, at)
        o, l, n = _by_block(body, first, last, block, q, q_idx, w_idx, chosen)
        out.append(jnp.moveaxis(o, 0, 1).reshape(o.shape[1], last - first, *o.shape[3:]))
        loss, pairs = loss + jnp.sum(l, axis=0), pairs + jnp.sum(n)
    return jnp.concatenate(out, axis=1), loss, pairs


def _sparse_kernel_core(q, k, v, q_idx, k_idx, w_idx, chosen, scale: float, block: int):
    """:func:`_sparse_core` (its arguments and result) with the heads' scores on the chip's fast memory
    (:mod:`gentun_tpu.models.sparse_kernel`): one fused kernel gives every head's output and log-sum-exp, its backward
    kernel dq, dk and dv, and no score reaches memory.  ``scale`` multiplies the float32 products on the tile, as
    :func:`_sparse_core`'s; the operands are handed over head-major, which is how :func:`_head_major` wrote them.  The loss pass stays XLA's, a query block at a time in :func:`_sparse_core`'s
    groups: a block's indexer scores again, and ``p`` -- the heads' mean share of each kept key, exactly 0 on every
    other pair -- from a third kernel that reads q, k, the log-sum-exp and the same bits (so the heads' scores a third
    time, and a fourth in the block's own rematerialisation: two products of the nine a tile costs).  All that is the
    heads' scores lies under the scope ``core``."""
    from . import sparse_kernel

    length, dims = q.shape[1], _sparse_kernel_dims(scale)
    block = min(block, length)
    with jax.named_scope("core"):
        heads_q = q.astype(k.dtype).transpose(0, 2, 3, 1, 4)
        heads_k, heads_v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        out, lse = sparse_kernel.core(heads_q, heads_k, heads_v, chosen, dims)
        out = out.transpose(0, 3, 1, 2, 4)
        seen = jax.lax.stop_gradient((heads_q, heads_k, lse))  # ``p`` is a constant to the gradient

    @jax.checkpoint
    def one_block(qib, kib, wb, bits, first):
        with jax.named_scope("indexer_scores"):
            index = _indexer_scores(qib, kib, wb, first)
        with jax.named_scope("core"):
            kept = _unpacked(bits, kib.shape[1])
            share = sparse_kernel.heads_share(*seen, chosen, first, block, kib.shape[1], dims)
        with jax.named_scope("indexer_loss"):
            loss = _indexer_loss(index, share, kept)
        return loss, jnp.sum(kept, dtype=jnp.int32)

    loss, pairs = 0.0, 0
    for first, last in _sparse_blocks(length, block):
        body = lambda qib, wb, bits, at, last=last: one_block(qib, k_idx[:, :last], wb, bits, at)
        l, n = _by_block(body, first, last, block, q_idx, w_idx, chosen)
        loss, pairs = loss + jnp.sum(l, axis=0), pairs + jnp.sum(n)
    return out, loss, pairs


def _indexer_reads(x):
    """What the indexer reads of the layer's normed input: its value, DETACHED -- the indexer
    learns from its own loss and moves nothing it reads."""
    return jax.lax.stop_gradient(x)


def _indexer_operands(indexer, x, cfg: Lfm2MoeConfig, dtype, positions=None):
    """(``qI`` (sequences, length, heads, size) and ``kI`` (sequences, length, size) in the
    compute dtype, ``w`` (sequences, length, heads) float32 times ``heads^-0.5 size^-0.5``) of a
    ``sparse_attention`` layer's indexer (``q`` hidden x (heads x size), ``k`` hidden x size, ONE
    key head for all its heads, ``w`` hidden x heads) from the layer's normed input, detached
    (:func:`_indexer_reads`): products in the compute dtype accumulated in float32; plain rope on
    every column of its queries and of its key at the first stream's positions."""
    hidden, ni, di = x.shape[-1], cfg.indexer_num_heads, cfg.indexer_head_dim
    theta, _ = cfg.rope_of("sparse_attention")
    seen = _indexer_reads(x).astype(dtype)
    product = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    q_idx = product("slh,hjd->sljd", seen, indexer["q"].astype(dtype).reshape(hidden, ni, di))
    k_idx = product("slh,hd->sld", seen, indexer["k"].astype(dtype))
    w_idx = product("slh,hj->slj", seen, indexer["w"].astype(dtype)) * (ni ** -0.5 * di ** -0.5)
    q_idx = _rope(q_idx, theta, positions=positions).astype(dtype)
    k_idx = _rope(k_idx[:, :, None], theta, positions=positions)[:, :, 0].astype(dtype)
    return q_idx, k_idx, w_idx


def _sparse_attention(p, indexer, x, cfg: Lfm2MoeConfig, dtype, positions=None):
    """Causal GQA whose keys a learned indexer chooses (DeepSeek Sparse Attention's indexer over a
    grouped-query trunk), on (sequences, length, hidden).  The trunk is :func:`_attention`'s:
    q, k, v head-major, the per-head norm of q and k where the configuration has one, rope on
    whole heads -- by sections over the position streams where it has ``mrope_section``
    (``positions`` (streams, length); None: the token's index in each).  The indexer's operands
    (:func:`_indexer_operands`) come from the layer's normed input detached.  Then the selection
    (:func:`_sparse_selection`: the keys whose score reaches the ``sparse_topk``-th largest among a
    query's keys, as bits) and the core with the indexer's loss: the fused kernels where
    :func:`_use_sparse_kernel` says so (:func:`_sparse_kernel_core`), XLA's query blocks elsewhere
    (:func:`_sparse_core`) -- one function, chosen by backend and shape; the selection, the core's
    output and the kernels' log-sum-exp are kept for the backward pass under rematerialisation
    (``SPARSE_KEPT``).  Scopes: ``proj``, ``rope``, ``indexer_proj``,
    ``indexer_scores``, ``select``, ``core``, ``indexer_loss``.

    Returns (the output, (the indexer's loss: the mean over queries of the KL term, float32; the
    (query, key) pairs kept, int32))."""
    from jax.ad_checkpoint import checkpoint_name

    hidden, nkv, hd = x.shape[-1], cfg.num_key_value_heads, cfg.head_dim
    nh = p["o"].shape[0] // hd
    theta, scaling = cfg.rope_of("sparse_attention")
    with jax.named_scope("proj"):
        q = _head_major(x, p["q"].astype(dtype).reshape(hidden, nkv, nh // nkv, hd))
        k = _head_major(x, p["k"].astype(dtype).reshape(hidden, nkv, hd))
        v = _head_major(x, p["v"].astype(dtype).reshape(hidden, nkv, hd))
    with jax.named_scope("rope"):
        normed = lambda a, weight: _rms_norm(a, p[weight], cfg.norm_eps) if cfg.qk_norm else a
        turned = functools.partial(_rope_whole_heads, theta=theta, scaling=scaling, sections=cfg.mrope_section,
                                   positions=positions)
        q, k = turned(normed(q, "q_norm")), turned(normed(k, "k_norm")).astype(dtype)
    with jax.named_scope("indexer_proj"):
        q_idx, k_idx, w_idx = _indexer_operands(indexer, x, cfg, dtype, positions)
    chosen = checkpoint_name(_sparse_selection(q_idx, k_idx, w_idx, cfg.sparse_topk, cfg.attn_block), SPARSE_KEPT[0])
    if _use_sparse_kernel(x.shape[1], nh // nkv, hd, cfg.attn_block):  # the kernels name what they keep themselves
        out, loss, pairs = _sparse_kernel_core(q, k, v, q_idx, k_idx, w_idx, chosen, 1.0 / math.sqrt(hd), cfg.attn_block)
    else:
        out, loss, pairs = _sparse_core(q, k, v, q_idx, k_idx, w_idx, chosen, 1.0 / math.sqrt(hd), cfg.attn_block)
        out = checkpoint_name(out, SPARSE_KEPT[1])
    with jax.named_scope("proj"):
        out = jnp.einsum("slngd,ngdh->slh", out, p["o"].astype(dtype).reshape(nkv, nh // nkv, hd, hidden))
    return out, (jnp.sum(loss) / (x.shape[0] * x.shape[1]), pairs)


def latent_softmax_scale(cfg: Lfm2MoeConfig) -> float:
    """``(nope + rope)^-0.5``, times ``m^2`` under YaRN (``m`` from ``mscale_all_dim``)."""
    m = yarn_mscale(cfg.yarn["factor"], cfg.yarn["mscale_all_dim"]) if cfg.yarn else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _head_major(x, w):
    """``x`` (sequences, length, in) times ``w`` (in, *heads, size), handed on as
    (sequences, length, *heads, size) and written as (sequences, *heads, length,
    size): the product emits the order the fused core reads, and the transpose
    back to the token-major shape is a change of layout that XLA:TPU carries to
    the consumer, not a pass over memory."""
    return jnp.moveaxis(jnp.einsum("slh,h...d->s...ld", x.astype(w.dtype), w), -2, 1)


def _latent_attention(p, x, cfg: Lfm2MoeConfig, dtype):
    """Causal latent attention (MLA, no query latent) on (sequences, length,
    hidden), as training runs it: keys and values expanded from the latent per
    head, the rope part of the key one head that every query head shares (a
    broadcast: its gradient is the sum over the heads), and the same causal core
    as :func:`_attention` at ``heads`` key-value heads of one query head each.

    What crosses to :func:`_causal_core` is q (sequences, length, heads, 1,
    nope + rope) and k (sequences, length, heads, nope + rope), ``[nope | rope]``
    along the last axis, and v (sequences, length, heads, v size), all in the
    compute dtype and each assembled once: ``W_q`` and ``W_kvb`` are applied as
    their nope, rope and value column blocks, each product head-major
    (:func:`_head_major`); rope is float32 arithmetic on the rope columns alone;
    the concatenations, the shared key's broadcast, the core's zero columns, scale
    and cast are one pass an operand (under ``core``)."""
    s, length, hidden = x.shape
    nh, rank, nope, rope, vd = (cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                                cfg.qk_rope_head_dim, cfg.v_head_dim)
    with jax.named_scope("down_proj"):
        # one query head a key-value head: the core's group axis, in the weights' shape so that no reshape follows
        w_q = p["q"].astype(dtype).reshape(hidden, nh, 1, nope + rope)
        q_nope, q_pe = _head_major(x, w_q[..., :nope]), _head_major(x, w_q[..., nope:])
        latent = _dot(x, p["kva"], dtype)
        c = _rms_norm(latent[..., :rank], p["kv_norm"], cfg.norm_eps).astype(dtype)
    with jax.named_scope("up_proj"):
        w_kvb = p["kvb"].astype(dtype).reshape(rank, nh, nope + vd)
        k_nope, v = _head_major(c, w_kvb[..., :nope]), _head_major(c, w_kvb[..., nope:])
    with jax.named_scope("rope"):
        q_pe = _rope(q_pe.astype(jnp.float32), cfg.rope_theta, cfg.yarn).astype(dtype)
        k_pe = _rope(latent[..., None, rank:].astype(jnp.float32), cfg.rope_theta, cfg.yarn).astype(dtype)
    with jax.named_scope("core"):
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, length, nh, rope))], axis=-1)
        out = _causal_core(q, k, v, latent_softmax_scale(cfg), cfg)
    with jax.named_scope("out_proj"):
        w_o = p["o"].astype(dtype).reshape(nh, vd, hidden)
        return jnp.einsum("slnd,ndh->slh", out.reshape(s, length, nh, vd), w_o)


_EXACT = dict(precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


@jax.custom_vjp
def _affine_scan(a, b):
    """The state that *entered* each step of ``S_i = a_i S_{i-1} + b_i`` from an
    empty one: ``a`` (steps, ..., n, n), ``b`` and the result (steps, ..., n, m),
    float32.  One product and one add a step, forward and backward: the reverse
    scan carries ``dS_{i-1} = a_i^T dS_i + (what was sent to the state that
    entered step i)`` and stacks ``dS``, which is ``db``; ``da_i = dS_i
    S_{i-1}^T`` is one batched product after it.  Kept for the backward pass:
    ``a`` and the stacked states, which are the result itself.  ``a`` may be one
    scalar a state, (steps, ...): the product is then a scaling, and ``da_i``
    the sum of ``dS_i * S_{i-1}`` over the state (a ``mamba2`` layer's decay)."""
    def step(state, a_b):
        return _decayed(a_b[0], state, "...de,...ef->...df") + a_b[1], state

    return jax.lax.scan(step, jnp.zeros_like(b[0]), (a, b))[1]


def _decayed(a, state, product: str):
    """``a`` applied to ``state``: ``product`` where it is a matrix a state, a scaling where it is a scalar."""
    if a.ndim == state.ndim:
        return jnp.einsum(product, a, state, **_EXACT)
    return a[..., None, None] * state


def _affine_scan_fwd(a, b):
    entered = _affine_scan(a, b)
    return entered, (a, entered)


def _affine_scan_bwd(kept, sent):
    a, entered = kept

    def step(left, a_sent):  # ``left``: the cotangent of the state that left this step
        return _decayed(a_sent[0], left, "...de,...df->...ef") + a_sent[1], left

    left = jax.lax.scan(step, jnp.zeros_like(sent[0]), (a, sent), reverse=True)[1]
    if a.ndim < entered.ndim:
        return jnp.sum(left * entered, axis=(-2, -1)), left
    return jnp.einsum("...df,...ef->...de", left, entered, **_EXACT), left


_affine_scan.defvjp(_affine_scan_fwd, _affine_scan_bwd)


def _delta_core_xla(q, k, v, g, beta, chunk: int):
    """The gated delta rule in chunks, as XLA's ops.  Per value head, from ``S_0 = 0``::

        S' = exp(g_t) S_{t-1};   S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;   o_t = S_t^T q_t

    ``q``, ``k`` (sequences, length, key heads, key size), already normed and
    scaled; ``v`` (sequences, length, key heads, value heads a key head, value
    size); ``g <= 0`` and ``beta`` (sequences, length, key heads, value heads a
    key head); all float32, and so is every product here (HIGHEST: the state
    and what multiplies it never pass through the compute dtype).  Returns
    ``o`` in ``v``'s shape.

    Inside a chunk of ``chunk`` positions, with ``G_i`` the running sum of ``g``
    from the chunk's start (so ``G`` falls) and ``D_ij = exp(G_i - G_j)`` for
    ``j <= i``, the updates of the chunk are one unit lower-triangular system
    (the WY / UT form): ``(I + tril(beta_i (k_i . k_j) D_ij, -1)) [U | W] =
    [beta v | beta exp(G) k]`` -- ``U`` the values each position writes if the
    chunk started from an empty state, ``W`` what it reads of the state it did
    start from -- solved once for all chunks.  With ``K = exp(G_last - G) k``
    and ``P = tril((q . k) D)`` a chunk maps the state ``S`` that enters it to::

        S <- exp(G_last) S + K^T (U - W S)  =  A S + B,   A = exp(G_last) I - K^T W,   B = K^T U
        o  = exp(G) q S + P (U - W S)       =  (exp(G) q - P W) S + P U

    ``A`` and ``B`` (key size x key size and key size x value size a head and
    chunk), ``exp(G) q - P W`` and ``P U`` need no state: they are batched
    products over all chunks, each written once.  Only ``S <- A S + B`` runs in
    sequence (:func:`_affine_scan`: ``LINEAR_CORE_CHAIN_PRODUCTS`` product a
    step, no ``exp``), and the outputs are one more batched product with the
    stacked states.  Every exponent is a difference ``G_i - G_j`` with ``j <=
    i``, a ``G_i`` or ``G_last - G_i``: none is positive, so nothing overflows
    however strong the decay (an ``exp(-G)`` on its own would).  A length that
    is no whole number of chunks is padded with positions that write nothing
    (``beta = 0``, ``g = 0``).

    The backward pass has the same shape: jax differentiates the batched
    passes as they are, and the scan's own rule carries one cotangent of the
    state back through the chunks.  It keeps the state that entered each chunk
    (float32, key size x value size a head) and ``A`` beside the batched
    passes' operands (``[U | W]``, ``K``, ``exp(G) q - P W``, ``P``).
    """
    s, length, n, dk = q.shape
    r, dv = v.shape[3], v.shape[4]
    pad = -length % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (q, k, v, g, beta))
    chunks = (length + pad) // chunk
    # (chunks, sequences, key heads, [value heads a key head,] positions, size): the chunk leads from here on, so
    # that the scan takes its operands as they lie and XLA copies none of them into place
    q, k = (a.reshape(s, chunks, chunk, n, dk).transpose(1, 0, 3, 2, 4) for a in (q, k))
    v = v.reshape(s, chunks, chunk, n, r, dv).transpose(1, 0, 3, 4, 2, 5)
    g, beta = (a.reshape(s, chunks, chunk, n, r).transpose(1, 0, 3, 4, 2) for a in (g, beta))
    fall = jnp.cumsum(g, axis=-1)
    at_or_before = jnp.tril(jnp.ones((chunk, chunk), bool))
    apart = fall[..., :, None] - fall[..., None, :]
    decay = jnp.where(at_or_before, jnp.exp(jnp.where(at_or_before, apart, 0.0)), 0.0)
    kk = jnp.einsum("Nsnid,Nsnjd->Nsnij", k, k, **_EXACT)[:, :, :, None]
    qk = jnp.einsum("Nsnid,Nsnjd->Nsnij", q, k, **_EXACT)[:, :, :, None] * decay
    system = jnp.eye(chunk, dtype=jnp.float32) + jnp.tril(beta[..., None] * kk * decay, -1)
    wrote = jnp.concatenate([beta[..., None] * v, (beta * jnp.exp(fall))[..., None] * k[:, :, :, None]], axis=-1)
    solved = jax.lax.linalg.triangular_solve(system, wrote, left_side=True, lower=True, unit_diagonal=True)
    values, reads = solved[..., :dv], solved[..., dv:]  # U and W
    # what needs no state, for all chunks at once: A, B and the outputs' two operands, each product written once
    k_to_end = jnp.exp(fall[..., -1:] - fall)[..., None] * k[:, :, :, None]
    kept = jnp.exp(fall[..., -1])[..., None, None] * jnp.eye(dk, dtype=jnp.float32) \
        - jnp.einsum("Nsnrcd,Nsnrce->Nsnrde", k_to_end, reads, **_EXACT)
    added = jnp.einsum("Nsnrcd,Nsnrce->Nsnrde", k_to_end, values, **_EXACT)
    q_seen = jnp.exp(fall)[..., None] * q[:, :, :, None] - jnp.einsum("Nsnrij,Nsnrjd->Nsnrid", qk, reads, **_EXACT)
    entered = _affine_scan(kept, added)
    out = jnp.einsum("Nsnrcd,Nsnrde->Nsnrce", q_seen, entered, **_EXACT) \
        + jnp.einsum("Nsnrij,Nsnrje->Nsnrie", qk, values, **_EXACT)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(s, chunks * chunk, n, r, dv)  # (chunks, s, n, r, positions, dv) back
    return out[:, :length]


def _use_delta_kernel(dk: int, dv: int, chunk: int, heads: int) -> bool:
    """Whether the delta rule's core of a program traced now runs as the fused
    kernels (:mod:`gentun_tpu.models.delta_kernel`): the backend is a TPU, a
    head's key and value columns are whole lanes, a chunk whole sublanes, a
    chunk of a key head's ``heads`` value heads fits the chip's fast memory,
    and this jax ships Pallas.  A rule by backend and shape, as :func:`_use_attention_kernel`'s."""
    if jax.default_backend() != "tpu":
        return False
    try:
        from . import delta_kernel
    except ImportError:
        return False
    return delta_kernel.fits(dk, dv, chunk, heads)


def _delta_core(q, k, v, g, beta, chunk: int):
    """The delta rule's core (:func:`_delta_core_xla`'s arguments and result):
    the fused kernels where :func:`_use_delta_kernel` says so, XLA's ops
    elsewhere: one function, chosen by backend and shape.  Both are the
    ``chunked`` program: the rule in chunks with a state carried between them."""
    if _use_delta_kernel(q.shape[-1], v.shape[-1], chunk, v.shape[3]):
        from . import delta_kernel
        return delta_kernel.delta_core(q, k, v, g, beta, chunk)
    return _delta_core_xla(q, k, v, g, beta, chunk)


def _unit_rows(a):
    """``a`` over its l2 norm along the last axis (``a / sqrt(sum a^2 + L2_EPS)``), float32."""
    return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)


def _linear_attention(p, x, cfg: Lfm2MoeConfig, dtype):
    """Gated DeltaNet on (sequences, length, hidden): one in-projection to
    ``[q | k | v | z]`` (the key heads' q and k, the value heads' v and output
    gate z, each a block of columns) and one to ``[b | a]`` (a write strength
    and a decay a value head, float32 like a router's scores); a causal
    depthwise convolution with SiLU over the q, k and v columns (float32
    arithmetic on the compute dtype's product, zeros before position 0);
    ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q and k
    l2-normed a head, q over the root of the key size; the delta rule
    (:func:`_delta_core`), each key head serving ``value heads / key heads``
    value heads; ``RMSNorm(o) * silu(z)`` a head, one norm weight for all; the
    out-projection.  Scopes: ``proj``, ``conv``, ``gates``, ``core``, ``norm_gate``."""
    s, length, _ = x.shape
    nk, nv, dk, dv = (cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
    keys, values = _delta_widths(cfg)
    with jax.named_scope("proj"):
        qkvz = _dot(x, p["qkvz"], dtype)
    with jax.named_scope("gates"):
        ba = jnp.dot(x.astype(jnp.float32), p["ba"], precision=jax.lax.Precision.HIGHEST).reshape(s, length, 2, nk, nv // nk)
        beta = jax.nn.sigmoid(ba[:, :, 0])
        g = -jnp.exp(p["A_log"]).reshape(nk, -1) * jax.nn.softplus(ba[:, :, 1] + p["dt_bias"].reshape(nk, -1))
    with jax.named_scope("conv"):
        taps = cfg.linear_conv_kernel_dim
        padded = jnp.pad(qkvz[..., :2 * keys + values], ((0, 0), (taps - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(p["kernel"][:, j] * padded[:, j:j + length].astype(jnp.float32) for j in range(taps)))
    with jax.named_scope("core"):
        q = _unit_rows(mixed[..., :keys].reshape(s, length, nk, dk)) * dk ** -0.5
        k = _unit_rows(mixed[..., keys:2 * keys].reshape(s, length, nk, dk))
        v = mixed[..., 2 * keys:].reshape(s, length, nk, nv // nk, dv)
        out = _delta_core(q, k, v, g, beta, cfg.delta_chunk)
    with jax.named_scope("norm_gate"):
        z = qkvz[..., 2 * keys + values:].reshape(out.shape).astype(jnp.float32)
        out = (_rms_norm(out, p["norm"], cfg.norm_eps) * jax.nn.silu(z)).astype(dtype)
    with jax.named_scope("proj"):
        return _dot(out.reshape(s, length, values), p["out"], dtype)


def _state_space_core(x, b, c, step, rate, chunk: int):
    """The Mamba-2 recurrence (state-space duality, arXiv:2405.21060) in chunks, as XLA's ops.  Per head, from
    ``S_0 = 0`` in R^(head size x state size), with ``a_t = exp(step_t * rate)``, ``rate < 0``::

        S_t = a_t S_{t-1} + step_t x_t B_t';     y_t = S_t C_t

    ``x`` (sequences, length, groups, heads a group, head size); ``b``, ``c`` (sequences, length, groups, state
    size), one pair a group of heads; ``step > 0`` (sequences, length, groups, heads a group); ``rate`` (groups,
    heads a group); all float32, and so is every product here (HIGHEST).  Returns ``y`` in ``x``'s shape.

    Inside a chunk of ``chunk`` positions, with ``L_i`` the running sum of ``step * rate`` from the chunk's start (so
    ``L`` falls), a chunk maps the state ``S`` that enters it to::

        S <- exp(L_end) S + sum_j exp(L_end - L_j) step_j x_j B_j'            one scalar a head, and what the chunk adds
        y_i = exp(L_i) C_i S + sum_{j <= i} (C_i . B_j) exp(L_i - L_j) step_j x_j

    What a chunk adds needs no state: one batched product over all chunks.  Only ``S <- a S + b`` runs in sequence
    (:func:`_affine_scan` with a scalar ``a``: a scaling and an add a step, forward and backward), and the outputs
    are two more batched products made AFTER it, where they are used: ``C B'`` (once a group) masked by the decays
    is (chunks, heads, chunk, chunk) float32 and is never alive across the scan (PERF.md, PR 41: XLA's forms of a
    chunked scan pay for passes over such stacks, not for the steps).  Every exponent is a difference ``L_i - L_j``
    with ``j <= i``, an ``L_i`` or ``L_end - L_j``: none is positive.  A length that is no whole number of chunks is
    padded with positions that write nothing and decay nothing (``step = 0``)."""
    s, length, g, r, size = x.shape
    n = b.shape[-1]
    pad = -length % chunk
    if pad:
        x, b, c, step = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (x, b, c, step))
    chunks = (length + pad) // chunk
    # (chunks, sequences, groups, [heads a group,] positions, size): the chunk leads, as the scan takes its operands
    x = x.reshape(s, chunks, chunk, g, r, size).transpose(1, 0, 3, 4, 2, 5)
    b, c = (a.reshape(s, chunks, chunk, g, n).transpose(1, 0, 3, 2, 4) for a in (b, c))
    step = step.reshape(s, chunks, chunk, g, r).transpose(1, 0, 3, 4, 2)
    fall = jnp.cumsum(step * rate[..., None], axis=-1)  # L: (chunks, s, g, r, positions)
    written = step[..., None] * x  # what a position writes, before its decay
    to_end = jnp.exp(fall[..., -1:] - fall)[..., None] * written
    added = jnp.einsum("Nsgrcp,Nsgcn->Nsgrpn", to_end, b, **_EXACT)
    entered = _affine_scan(jnp.exp(fall[..., -1]), added)
    at_or_before = jnp.tril(jnp.ones((chunk, chunk), bool))
    apart = fall[..., :, None] - fall[..., None, :]
    decay = jnp.where(at_or_before, jnp.exp(jnp.where(at_or_before, apart, 0.0)), 0.0)
    seen = jnp.einsum("Nsgin,Nsgjn->Nsgij", c, b, **_EXACT)[:, :, :, None] * decay  # once a group, masked a head
    out = jnp.einsum("Nsgrij,Nsgrjp->Nsgrip", seen, written, **_EXACT) \
        + jnp.exp(fall)[..., None] * jnp.einsum("Nsgin,Nsgrpn->Nsgrip", c, entered, **_EXACT)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(s, chunks * chunk, g, r, size)
    return out[:, :length]


def _gated_norm(y, z, weight, eps):
    """``RMSNorm(y * silu(z); weight)`` over the last axis, a group's channels: the gate FIRST, then the norm."""
    return _rms_norm(y * jax.nn.silu(z), weight, eps)


def _state_space(p, x, cfg: Lfm2MoeConfig, dtype):
    """The share of a Mamba-2 mixer that the held heads give, on (sequences, length, hidden): one in-projection
    to ``[z | x | B | C | dt]`` (the held heads' gate and input, their groups' B and C -- head ``h`` reads group
    ``h // (heads / groups)`` -- and a step a head; the step's columns a float32 product of their own, like a
    router's scores); a causal depthwise convolution with a bias and SiLU over x, B and C (float32 arithmetic on
    the compute dtype's product, zeros before position 0); ``step = softplus(dt + dt_bias)``, the decay a position
    ``exp(step * -exp(A_log))``; the recurrence (:func:`_state_space_core`) plus the skip ``D x``;
    ``RMSNorm(y * silu(z))`` over each group's channels -- the gate first -- and the out-projection's rows of the
    held heads.  What the absent heads would add to the sum is left out.  Scopes: ``proj``, ``conv``, ``gates``,
    ``core``, ``norm_gate``."""
    s, length, _ = x.shape
    (heads, groups), (inner, mixed) = cfg.mamba_held, _mamba_widths(cfg)
    size, state = cfg.mamba_head_dim, cfg.ssm_state_size
    with jax.named_scope("proj"):
        zxbc = _dot(x, p["in_proj"][:, :inner + mixed], dtype)
    with jax.named_scope("gates"):
        dt = jnp.dot(x.astype(jnp.float32), p["in_proj"][:, inner + mixed:], precision=jax.lax.Precision.HIGHEST)
        step = jax.nn.softplus(dt + p["dt_bias"]).reshape(s, length, groups, heads // groups)
        rate = -jnp.exp(p["A_log"]).reshape(groups, heads // groups)
    with jax.named_scope("conv"):
        taps = cfg.mamba_conv_kernel
        padded = jnp.pad(zxbc[..., inner:], ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(p["kernel"][:, j] * padded[:, j:j + length].astype(jnp.float32) for j in range(taps))
        conv = jax.nn.silu(conv + p["conv_bias"])
    with jax.named_scope("core"):
        u = conv[..., :inner].reshape(s, length, groups, heads // groups, size)
        b, c = (conv[..., lo:lo + groups * state].reshape(s, length, groups, state)
                for lo in (inner, inner + groups * state))
        out = _state_space_core(u, b, c, step, rate, cfg.mamba_chunk) + p["D"].reshape(groups, -1, 1) * u
    with jax.named_scope("norm_gate"):
        by_group = (s, length, groups, inner // groups)
        out = _gated_norm(out.reshape(by_group), zxbc[..., :inner].astype(jnp.float32).reshape(by_group),
                          p["norm"].reshape(by_group[2:]), cfg.norm_eps).astype(dtype)
    with jax.named_scope("proj"):
        return _dot(out.reshape(s, length, inner), p["out"], dtype)


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


def _dense_ffn(p, x, dtype):
    """A SwiGLU of three matrices, or, of a tree without the gate's ``w3``, ``W_2 relu2(W_1 x)``."""
    if "w3" not in p:
        return _dot(_relu2(_dot(x, p["w1"], dtype)), p["w2"], dtype)
    return _dot(jax.nn.silu(_dot(x, p["w1"], dtype)) * _dot(x, p["w3"], dtype), p["w2"], dtype)


def _route(router, bias, x, cfg: Lfm2MoeConfig):
    """Scores over ALL experts (``scoring_func``: a sigmoid each, or one softmax),
    the chosen top-k (by score, plus the bias where the balance rule is one) and
    their weights: the chosen scores alone, divided by their sum where
    ``norm_topk_prob`` says so.  Returns (chosen, weights, scores); float32."""
    logits = jnp.dot(x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if cfg.scoring_func == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(scores + bias if cfg.balance_rule == "bias" else scores, cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:  # LFM2's published rule guards its sum of sigmoids; a sum of softmax shares needs none
        picked = picked / (picked.sum(-1, keepdims=True) + (cfg.route_eps if cfg.scoring_func == "sigmoid" else 0.0))
    return chosen, picked, scores


def _balance_term(scores, chosen, sequences: int, cfg: Lfm2MoeConfig):
    """The sequence-wise balance term of a routed layer, before its weight:
    mean over sequences of ``sum_e f_e P_e`` over ALL experts, ``f_e = experts /
    (k L)`` times the tokens of the sequence that chose ``e`` (a count: no
    gradient) and ``P_e`` the sequence's mean score.  1 where routing is even."""
    experts, k = cfg.num_experts, cfg.num_experts_per_tok
    per_sequence = chosen.reshape(sequences, -1)
    count = jnp.sum(per_sequence[..., None] == jnp.arange(experts), axis=1, dtype=jnp.float32)
    f = count * (experts / per_sequence.shape[1])  # L k assignments a sequence
    mean_score = scores.reshape(sequences, -1, experts).mean(axis=1)
    return jnp.mean(jnp.sum(jax.lax.stop_gradient(f) * mean_score, axis=-1))


class RoutedStats(NamedTuple):
    """What the routed layers of a call report beside their loads (each adds up over layers)."""

    dropped: Any  # int32: held assignments that found no room in the row buffer: 0, the height taken holds them
    heights: Any  # int32 (rungs,): routed layers that took each of :func:`_row_buffer_heights`, shortest first
    balance: Any  # float32: the balance terms (``aux_loss`` rule; 0 under the bias rule, which has none)
    # of a model with ``sparse_attention`` layers (None, no leaf, in every other): the indexers' losses, float32, and
    # the (query, key) pairs each such layer kept, int32 (one a layer in what :func:`forward` returns)
    indexer_loss: Any = None
    selected: Any = None

    @property
    def wide(self):
        """int32: routed layers that took the worst-case height where there is a shorter one."""
        return self.heights[-1] * (len(self.heights) > 1)


def _row_buffer_heights(cfg: Lfm2MoeConfig, tokens: int) -> Tuple[int, ...]:
    """The row buffer's heights, shortest first: the smallest multiples of the
    ``gmm`` row tile that hold ``_ROW_BUFFER_SHARES`` times the rows this rank
    gets on average, those under the worst case, and the worst case, which a
    small shape has alone: ``min(top-k, experts held) x tokens`` rows, for a
    token's choices are distinct experts and reach a held one once (8 held of
    512 under a top-22: 8 rows a token, not 22)."""
    full, tile = min(cfg.num_experts_per_tok, cfg.n_held) * tokens, _GMM_TILING[0]
    share = cfg.num_experts_per_tok * tokens * cfg.n_held / cfg.num_experts
    below = sorted({tile * math.ceil(s * share / tile) for s in _ROW_BUFFER_SHARES})
    return tuple(h for h in below if h < full) + (full,)


def _expert_rows(cfg: Lfm2MoeConfig, dtype, cap: int, p, x, weight, order, sizes):
    """Dispatch, experts and combine on a row buffer of the static height
    ``cap``: the first ``cap`` assignments of ``order`` (held ones first, grouped
    by expert; ``sizes`` a held expert) each get a row.  Every pass has the
    buffer's height.  ``x`` is what the experts read and their sum's width: the
    residual stream's state, or the latent one.  Returns the tokens' sums and
    the rows that found room."""
    t, h = x.shape
    k = cfg.num_experts_per_tok
    with jax.named_scope("moe"), jax.named_scope("dispatch"):
        ends = jnp.minimum(jnp.cumsum(sizes), cap)
        sizes_kept = jnp.diff(ends, prepend=0).astype(jnp.int32)
        n_rows = ends[-1]
        filled = (jnp.arange(cap) < n_rows)[:, None]
        taken = order[:cap]  # the assignment a row holds: token a // k, choice a % k
        rows = jnp.where(filled, jnp.take(x, taken // k, axis=0), 0).astype(dtype)
    with jax.named_scope("moe"), jax.named_scope("experts"):
        if cfg.gated_experts:
            up = jax.nn.silu(_grouped_matmul(rows, p["w1"].astype(dtype), sizes_kept)) \
                * _grouped_matmul(rows, p["w3"].astype(dtype), sizes_kept)
        else:  # two matrices an expert and no gate
            up = _relu2(_grouped_matmul(rows, p["w1"].astype(dtype), sizes_kept))
        down = _grouped_matmul(jnp.where(filled, up, 0), p["w2"].astype(dtype), sizes_kept)
        down = jnp.where(filled, down, 0)
    with jax.named_scope("moe"), jax.named_scope("combine"):
        # a token's at most top-k rows, each times its float32 weight, summed in float32
        scaled = jnp.take(weight.reshape(-1), taken)[:, None] * down
        out = jnp.zeros((t, h), jnp.float32).at[taken // k].add(scaled).astype(dtype)
    return out, n_rows


def _traced_once(body):
    """``body`` for callers that hand it operands of one shape again and again
    within one trace of a program (the routed layers of a model): the first
    call traces it, every later one replays the jaxpr under the scopes open at
    that call.  ``body`` closes over no array."""
    traced = {}

    def replay(*operands):
        flat, tree = jax.tree_util.tree_flatten(operands)
        key = (tree, tuple((a.shape, a.dtype) for a in flat))
        if key not in traced:
            closed, out = jax.make_jaxpr(body, return_shape=True)(*operands)
            traced[key] = closed, jax.tree_util.tree_structure(out)
        closed, out_tree = traced[key]
        return jax.tree_util.tree_unflatten(out_tree, jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *flat))

    return replay


def _expert_rows_by_count(heights: Tuple[int, ...], cfg: Lfm2MoeConfig, dtype):
    """:func:`_expert_rows` at the height of ``heights`` that the first operand,
    a rung's index, names: decided on the device.

    One ``switch`` forward and one backward, each branch differentiated inside
    itself from the layer's inputs.  Differentiated from outside, a conditional
    hands its backward pass every array any branch keeps, an absent branch's as
    zeros of that branch's height: at the published widths 2.8 GB more
    temporaries a step for one more branch (TPU compiler's memory analysis,
    PR 29) and a zero-fill of the taller buffers in every shorter pass.

    A height's body, forward and backward, is traced once for the callers that
    share what this returns (:func:`forward` hands one to all its layers): the
    trace of the grouped products' kernels is most of what a height costs a
    process before it compiles or loads anything, and the routed layers of a
    model have one shape (PERF.md, PR 39)."""

    def bodies(cap):
        forward = functools.partial(_expert_rows, cfg, dtype, cap)

        def backward(p, x, weight, order, sizes, g):
            _, vjp, _ = jax.vjp(lambda p, x, weight: forward(p, x, weight, order, sizes), p, x, weight, has_aux=True)
            return vjp(g)

        return _traced_once(forward), _traced_once(backward)

    forwards, backwards = zip(*map(bodies, heights))

    def primal(rung, *operands):
        return jax.lax.switch(rung, forwards, *operands)

    def cotangents(operands, g):  # of the weights, the tokens and the routing weights; rung, order and sizes have none
        rung, *operands = operands
        return (None,) + jax.lax.switch(rung, backwards, *operands, g[0]) + (None, None)

    by_count = jax.custom_vjp(primal)
    by_count.defvjp(lambda *operands: (primal(*operands), operands), cotangents)
    return by_count


def _moe_ffn(p, bias, x, cfg: Lfm2MoeConfig, dtype, row_buffer: Optional[int] = None, sequences: int = 1,
             by_count=_expert_rows_by_count):
    """The held experts' part of the routed feed-forward on (tokens, hidden),
    times ``routed_scaling_factor``, plus the shared experts where the
    configuration has them (every rank computes those alike).  With
    ``moe_latent_size`` the router and the shared expert read the full state,
    and the routed experts a down-projection of it (``moe/latent_in``, before
    dispatch); their weighted sum is formed in that latent state and projected
    up once (``moe/latent_out``): both projections are whole on every rank.

    Returns ``(out, load, stats)``: ``load`` counts the tokens each of ALL experts
    was chosen for (the bias rule needs them all), ``stats`` is this layer's
    :class:`RoutedStats` (its balance term is over ``sequences`` sequences of
    equal length).  The row buffer's height follows the rows present:
    the held assignments the router counted pick on the device the first of
    :func:`_row_buffer_heights` that holds them, the worst case of top-k x
    tokens rows at the latest; :func:`_expert_rows` is the body at every
    height, so no assignment is ever dropped.  ``row_buffer`` stands in for
    the heights below the worst case in the tests of that arithmetic and no
    caller sets it; ``by_count`` builds the function that chooses, and a
    caller with several layers hands in one that builds it once
    (:func:`_expert_rows_by_count`).  The ``switch`` is called outside the ``moe`` scope and
    each branch opens it again: jax names a branch's ops after the scopes open
    at the call (``layer2/cond/branch_0_fun/moe/experts/...``: a ``switch`` is
    the same conditional), and the benchmark's op classes go by what follows
    the first ``moe``."""
    t = x.shape[0]
    k, n_held = cfg.num_experts_per_tok, cfg.n_held
    worst = min(k, n_held) * t
    heights = _row_buffer_heights(cfg, t) if row_buffer is None else tuple(sorted({min(row_buffer, worst), worst}))
    with jax.named_scope("moe"), jax.named_scope("router"):
        chosen, weight, scores = _route(p["router"], bias, x, cfg)
        if cfg.routed_scaling_factor != 1.0:  # on the routed sum alone, as float32 weights: the shared experts' output is added as it is
            weight = weight * cfg.routed_scaling_factor
        load = jnp.sum(chosen[..., None] == jnp.arange(cfg.num_experts), axis=(0, 1), dtype=jnp.int32)
    balance = jnp.zeros((), jnp.float32)
    if cfg.balance_rule == "aux_loss":
        with jax.named_scope("aux_loss"):
            balance = _balance_term(scores, chosen, sequences, cfg)
    with jax.named_scope("moe"), jax.named_scope("dispatch"):
        local = chosen.reshape(-1) - cfg.held_experts[0]  # assignment a = token a // k, choice a % k
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held)
        order = jnp.argsort(key, stable=True)  # held assignments first, grouped by expert
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held), axis=0, dtype=jnp.int32)
        n_held_rows = sizes.sum()
        rung = jnp.sum(n_held_rows > jnp.asarray(heights[:-1], jnp.int32), dtype=jnp.int32)  # the first that holds them
    seen = x
    if cfg.moe_latent_size:  # the experts read, and add up in, a narrower state: routed on ``x``, dispatched from here
        with jax.named_scope("moe"), jax.named_scope("latent_in"):
            seen = _dot(x, p["latent_in"], dtype)
    operands = ({name: p[name] for name in (("w1", "w3", "w2") if cfg.gated_experts else ("w1", "w2"))},
                seen, weight, order, sizes)
    if len(heights) == 1:  # nothing to choose
        out, n_rows = _expert_rows(cfg, dtype, heights[0], *operands)
    else:
        out, n_rows = by_count(heights, cfg, dtype)(rung, *operands)
    if cfg.moe_latent_size:
        with jax.named_scope("moe"), jax.named_scope("latent_out"):
            out = _dot(out, p["latent_out"], dtype)
    if "shared" in p:
        with jax.named_scope("moe"), jax.named_scope("shared"):
            shared = _dense_ffn(p["shared"], x, dtype)
            if "shared_gate" in p:  # one sigmoid a token, float32 as the router's scores are
                opened = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p["shared_gate"],
                                                precision=jax.lax.Precision.HIGHEST))
                shared = (opened[:, None] * shared).astype(dtype)
            out = out + shared
    taken = (jnp.arange(len(heights)) == rung).astype(jnp.int32)
    return out, load, RoutedStats(n_held_rows - n_rows, taken, balance)


def _mixer(cfg: Lfm2MoeConfig, index: int, dtype, p, x):
    """The first half of a layer, ``x + Op(RMSNorm(x))``, on (sequences, length, hidden); the whole of a layer
    that is a mixer alone."""
    kind, name = cfg.layer_types[index], f"layer{cfg.layer_ids[index]}"
    with jax.named_scope(name):
        normed = _rms_norm(x, p["op_norm"], cfg.norm_eps).astype(dtype)
        if kind == "conv":
            with jax.named_scope("conv_op"):
                return x + _conv_op(p["conv"], normed, cfg, dtype)
        elif kind == "latent_attention":
            with jax.named_scope("latent_attention"):
                return x + _latent_attention(p["latent"], normed, cfg, dtype)
        elif kind == "linear_attention":
            with jax.named_scope("linear_attention"):
                return x + _linear_attention(p["delta"], normed, cfg, dtype)
        elif kind == "mamba2":
            with jax.named_scope("mamba2"):
                return x + _state_space(p["mamba"], normed, cfg, dtype)
        elif kind == "sparse_attention":  # the one mixer that reports: (the output, (its indexer's loss, the pairs it kept))
            with jax.named_scope("sparse_attention"):
                out, report = _sparse_attention(p["attn"], p["indexer"], normed, cfg, dtype)
                return x + out, report
        with jax.named_scope(kind if cfg.typed_attention else "attention"):
            return x + _attention(p["attn"], normed, cfg, dtype, kind)


def _ffn(cfg: Lfm2MoeConfig, index: int, dtype, p, bias, h, by_count=_expert_rows_by_count):
    """The second half, ``h + FFN(RMSNorm(h))``: the output and, of a routed layer, (load, stats); the whole of
    a layer that is a feed-forward alone."""
    with jax.named_scope(f"layer{cfg.layer_ids[index]}"):
        normed = _rms_norm(h, p["ffn_norm"], cfg.norm_eps).astype(dtype)
        if "dense" in p:
            with jax.named_scope("dense_ffn"):
                return h + _dense_ffn(p["dense"], normed, dtype), None
        out, load, stats = _moe_ffn(p["moe"], bias, normed.reshape(-1, normed.shape[-1]), cfg, dtype,
                                    sequences=normed.shape[0], by_count=by_count)
        return h + out.reshape(h.shape), (load, stats)


def _layer(cfg: Lfm2MoeConfig, index: int, dtype, p, bias, x, by_count=_expert_rows_by_count):
    """One layer on (sequences, length, hidden); ``bias`` is the layer's router
    bias or None; ``by_count`` is :func:`_moe_ffn`'s.  Returns the output and,
    of a routed layer, (load, stats).  A layer of a model whose layers are one
    half each (:meth:`Lfm2MoeConfig.halves_of`) is that half: its one norm, its
    one residual add."""
    halves = cfg.halves_of(index)
    if cfg.layer_types[index] == "sparse_attention":  # a routed layer (:func:`_normalize_config`): its stats carry the mixer's report
        x, (indexer_loss, selected) = _mixer(cfg, index, dtype, p, x)
        x, (load, stats) = _ffn(cfg, index, dtype, p, bias, x, by_count)
        return x, (load, stats._replace(indexer_loss=indexer_loss, selected=selected))
    if "mixer" in halves:
        x = _mixer(cfg, index, dtype, p, x)
    return _ffn(cfg, index, dtype, p, bias, x, by_count) if "ffn" in halves else (x, None)


def forward(cfg: Lfm2MoeConfig, params, bias, tokens, remat: bool = False):
    """Float32 logits (sequences, length, held vocabulary), the load of ALL
    experts per routed layer (layers, experts) and the routed layers'
    :class:`RoutedStats`, summed."""
    dtype = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    rungs = len(_row_buffer_heights(cfg, tokens.size))
    loads, use = [], RoutedStats(jnp.zeros((), jnp.int32), jnp.zeros(rungs, jnp.int32), jnp.zeros((), jnp.float32),
                                 jnp.zeros((), jnp.float32) if cfg.sparse_layers else None)
    selected = []  # the pairs each ``sparse_attention`` layer kept
    by_count = functools.lru_cache(maxsize=None)(_expert_rows_by_count)  # one for the layers of this trace
    routed = cfg.moe_layers
    for i, p in enumerate(params["layers"]):
        fn = functools.partial(_layer, cfg, i, dtype, by_count=by_count)
        moe = i in routed
        layer_bias = bias[routed.index(i)] if moe else None
        if remat and cfg.layer_types[i] == "linear_attention":
            # the halves of this layer are rematerialised apart: what the scan's backward pass keeps (a state a
            # chunk and head) would else lie beside the expert rows' buffer at its worst-case height while the
            # feed-forward half is differentiated -- 2 GB past the chip at the published cut (TPU compiler)
            h = jax.checkpoint(functools.partial(_mixer, cfg, i, dtype))(p, x)
            x, aux = jax.checkpoint(functools.partial(_ffn, cfg, i, dtype, by_count=by_count))(p, layer_bias, h)
        elif remat and cfg.layer_types[i] == "sparse_attention":
            # the selection (a bit a query and key) and the core's output are kept: the backward pass neither selects
            # again nor runs the whole core's forward again
            x, aux = jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(*SPARSE_KEPT))(p, layer_bias, x)
        else:
            x, aux = (jax.checkpoint(fn) if remat else fn)(p, layer_bias, x)
        if moe:
            loads.append(aux[0])
            if aux[1].selected is not None:
                selected.append(aux[1].selected)
            use = jax.tree_util.tree_map(jnp.add, use, aux[1]._replace(selected=None))
    if selected:
        use = use._replace(selected=jnp.stack(selected))
    with jax.named_scope("head"):
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps).astype(dtype)
        head = params["embed" if cfg.tie_word_embeddings else "head"]
        logits = jnp.einsum("slh,vh->slv", x, head.astype(dtype), preferred_element_type=jnp.float32)
    return logits, jnp.stack(loads), use


def selected_keys(cfg: Lfm2MoeConfig, params, bias, tokens):
    """Which keys each query of each ``sparse_attention`` layer keeps on ``tokens`` (sequences,
    length), as the programs choose them (:func:`_sparse_selection`'s bits, unpacked): bool
    (sparse layers, sequences, length, length), entry [l, s, t, k] true where query ``t`` keeps key ``k``.  What a comparison of the chosen sets and the tests read; no
    program the evaluator runs holds such an array."""
    dtype = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    routed, masks, length = cfg.moe_layers, [], tokens.shape[1]
    for i, p in enumerate(params["layers"]):
        if cfg.layer_types[i] == "sparse_attention":
            normed = _rms_norm(x, p["op_norm"], cfg.norm_eps).astype(dtype)
            q_idx, k_idx, w_idx = _indexer_operands(p["indexer"], normed, cfg, dtype)
            masks.append(_unpacked(_sparse_selection(q_idx, k_idx, w_idx, cfg.sparse_topk, cfg.attn_block), length))
        x = _layer(cfg, i, dtype, p, bias[routed.index(i)] if i in routed else None, x)[0]
    return jnp.stack(masks)


def token_loss(logits, targets):
    """Next-token cross-entropy per position, float32."""
    with jax.named_scope("loss"):
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


# -- the compiled programs -----------------------------------------------------------------------


class Lfm2MoePrograms(NamedTuple):
    """The compiled callables of one configuration (:meth:`Lfm2MoeModel.compiled_programs`).

    ``init(base_key, genome_hash) -> state``: a fresh train state, a dict of
    ``params``/``m``/``v`` (one tree each, :func:`param_shapes`), ``bias``
    (routed layers, experts), ``rows`` (routed layers, held experts: rows
    routed so far), ``dropped`` and ``row_buffer_heights`` (:class:`RoutedStats`,
    summed over the steps so far: routed layers x steps at each height) and,
    under the ``aux_loss`` balance rule, ``aux_loss`` (the routed layers'
    balance terms before their weight, summed likewise).  ``train_step(state, x, y, batch_rows,
    genes, step) -> (state, loss, load)``: ``state`` is donated; ``x``/``y``
    are the whole token arrays, ``batch_rows`` (train_steps, batch_sequences)
    the sequences of every step, ``genes`` the float32 vector in the
    configuration's ``gene_names`` order, ``step`` the step number; ``loss`` is
    what was differentiated (the balance term included where there is one),
    ``load`` this step's rows per held expert per routed layer.  ``eval(params, bias, x, y, rows)
    -> loss per token`` of the sequences ``rows``.  ``attention_kernel_layers``:
    the attention layers (GQA and latent alike) whose core these programs run as the fused
    kernel (:func:`_use_attention_kernel`, decided when they were built): all or none.
    ``kernel_layers_by_mask``: the same by the core's mask, ``(("causal", n), ("window", n))``, a mask the
    configuration has no layer of left out;
    ``kernel_visits``: per mask that runs as the kernel, the block pairs the kernel visits a head
    and sequence (:func:`_kernel_visits`, as sorted items).
    ``linear_core_layers``: the ``linear_attention`` layers by the program their delta core runs as
    (``LINEAR_CORE_PROGRAMS``: ``(("chunked", n),)``), empty where the configuration has none;
    ``linear_core_kernel_layers``: those of them whose core these programs run as the fused kernels
    (:func:`_use_delta_kernel`, decided when they were built): all or none;
    ``linear_core_inverse_products``: the products of rows x rows x rows those kernels spend on a chunk's
    triangular inverse (:func:`gentun_tpu.models.delta_kernel.inverse_products`, the function that chose
    each level's form: a level at half the rows counts a half, one in closed form nothing), 0 where XLA's
    ops run, whose solve is a substitution.
    ``heads_by_mask``, ``rotary_by_mask``: per mask, the query heads of each of its layers and the columns
    of a head that its rope turns (a kernel's visits are a head's: the work of a mask's layers is theirs
    times these heads; 0 columns where the configuration has no positional encoding), whichever core runs.
    ``state_space_core_layers``: the ``mamba2`` layers by the program their core runs as
    (``STATE_SPACE_CORE_PROGRAMS``: ``(("chunked", n),)``), empty where the configuration has none.
    ``sparse_core_layers``: the ``sparse_attention`` layers by the program their core runs as
    (``SPARSE_CORE_PROGRAMS``: ``(("blockwise", n),)`` or ``(("kernel", n),)``, decided when they were built by
    :func:`_use_sparse_kernel`: all or none), ``sparse_core_visits``: what XLA's query blocks visit a head
    and sequence off their own table (:func:`_sparse_visits`, as sorted items: the selection and the loss pass walk it
    whichever core runs), and ``sparse_kernel_visits``: what the fused kernels visit off theirs
    (:func:`gentun_tpu.models.sparse_kernel.visits`), empty where XLA's blocks run; all empty where the configuration has none.
    Such a state also holds ``indexer_loss`` (the indexers' losses, summed over layers and steps) and
    ``selected_pairs`` (the (query, key) pairs each such layer kept, summed over the steps)."""

    config: Lfm2MoeConfig
    init: Any
    train_step: Any
    eval: Any
    attention_kernel_layers: int
    kernel_layers_by_mask: Tuple[Tuple[str, int], ...] = ()
    kernel_visits: Tuple[Tuple[str, Tuple[Tuple[str, int], ...]], ...] = ()
    linear_core_layers: Tuple[Tuple[str, int], ...] = ()
    heads_by_mask: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    rotary_by_mask: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    linear_core_kernel_layers: int = 0
    linear_core_inverse_products: float = 0.0
    state_space_core_layers: Tuple[Tuple[str, int], ...] = ()
    sparse_core_layers: Tuple[Tuple[str, int], ...] = ()
    sparse_core_visits: Tuple[Tuple[str, int], ...] = ()
    sparse_kernel_visits: Tuple[Tuple[str, int], ...] = ()


def _init_leaf(name: str, key, index: int, shape):
    """The start of the ``index``-th leaf (``name``: its key after its parent's), drawn from its own
    fold of ``key``: norm weights and ``dt_bias`` 1; ``A_log`` the log of a decay rate uniform on
    (0.001, ``DECAY_RATE_MAX``) (the ``qwen3_next`` model type's initialiser, whose rates start at
    0); every matrix, the convolution kernels and the embedding among them, normal with deviation
    ``INIT_STD``.  Of a ``mamba2`` layer (the ``nemotron_h`` model type's initialiser): ``A_log``
    the log of a rate uniform on (1, ``DECAY_RATE_MAX``), the skip ``D`` 1, the convolution's bias 0
    and ``dt_bias`` the inverse softplus of a step log-uniform on (``TIME_STEP_MIN``,
    ``TIME_STEP_MAX``), floored at ``TIME_STEP_FLOOR``."""
    if "['mamba']" in name and "norm" not in name and len(shape) == 1:
        key = jax.random.fold_in(key, index)
        if "A_log" in name:
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, minval=1.0, maxval=DECAY_RATE_MAX))
        if "dt_bias" in name:
            step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, minval=math.log(TIME_STEP_MIN),
                                              maxval=math.log(TIME_STEP_MAX)))
            step = jnp.maximum(step, TIME_STEP_FLOOR)
            return step + jnp.log(-jnp.expm1(-step))  # softplus of this is the step
        return jnp.ones(shape, jnp.float32) if "['D']" in name else jnp.zeros(shape, jnp.float32)
    if "norm" in name or "dt_bias" in name:
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(key, index)
    if "A_log" in name:
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, minval=1e-3, maxval=DECAY_RATE_MAX))
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


@functools.lru_cache(maxsize=8)
def _programs(cfg: Lfm2MoeConfig) -> Lfm2MoePrograms:
    shapes = param_shapes(cfg)
    lo, hi = cfg.held_experts
    n_moe = len(cfg.moe_layers)
    by_loss = cfg.balance_rule == "aux_loss"
    sparse = len(cfg.sparse_layers)

    def init(base_key, genome_hash):
        key = jax.random.fold_in(jax.random.fold_in(base_key, genome_hash[0]), genome_hash[1])
        leaves, tree = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
        params = [_init_leaf("".join(map(str, path[-2:])), key, i, shape) for i, (path, shape) in enumerate(leaves)]
        params = jax.tree_util.tree_unflatten(tree, params)
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
        state = {"params": params, "m": zeros(), "v": zeros(),
                 "bias": jnp.zeros((n_moe, cfg.num_experts), jnp.float32),
                 "rows": jnp.zeros((n_moe, cfg.n_held), jnp.int32), "dropped": jnp.zeros((), jnp.int32),
                 "row_buffer_heights": jnp.zeros(len(_row_buffer_heights(cfg, cfg.tokens_per_step)), jnp.int32)}
        if by_loss:
            state["aux_loss"] = jnp.zeros((), jnp.float32)
        if sparse:
            state.update(indexer_loss=jnp.zeros((), jnp.float32), selected_pairs=jnp.zeros(sparse, jnp.int32))
        return state

    def loss_fn(params, bias, x, y, balance_weight):
        logits, load, use = forward(cfg, params, bias, x, remat=True)
        loss = token_loss(logits, y).mean()
        if by_loss:
            loss = loss + balance_weight * use.balance
        if sparse:  # each indexer's loss at a weight of 1: it shares no parameter's gradient with any other term
            loss = loss + use.indexer_loss
        return loss, (load, use)

    def train_step(state, x_all, y_all, batch_rows, genes, step):
        rows = batch_rows[step]
        # the fifth gene belongs to the balance rule: the bias's step, or the balance term's weight
        log10_lr, warmup_frac, weight_decay, beta2, balance_gene = (genes[i] for i in range(len(GENE_NAMES)))
        (loss, (load, use)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], state["bias"], x_all[rows], y_all[rows], balance_gene)
        with jax.named_scope("optimizer"):
            t = (step + 1).astype(jnp.float32)
            lr = 10.0 ** log10_lr * jnp.minimum(1.0, t / jnp.maximum(warmup_frac * cfg.train_steps, 1.0))
            c1, c2 = 1.0 - ADAM_BETA1 ** t, 1.0 - beta2 ** t

            def adamw(path, p, m, v, g):
                m = ADAM_BETA1 * m + (1.0 - ADAM_BETA1) * g
                v = beta2 * v + (1.0 - beta2) * g * g
                decay = 0.0 if any(name in str(path[-1]) for name in _UNDECAYED) else weight_decay
                return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS) + decay * p), m, v

            updated = jax.tree_util.tree_map_with_path(adamw, state["params"], state["m"], state["v"], grads)
            params, m, v = jax.tree_util.tree_transpose(
                jax.tree_util.tree_structure(grads), jax.tree_util.tree_structure((0, 0, 0)), updated)
        bias = state["bias"]  # all zeros and never read under the ``aux_loss`` rule
        if not by_loss:
            with jax.named_scope("bias_update"):
                mean_load = cfg.tokens_per_step * cfg.num_experts_per_tok / cfg.num_experts
                bias = bias + balance_gene * jnp.sign(mean_load - load.astype(jnp.float32))
        held = load[:, lo:hi]
        new = {"params": params, "m": m, "v": v, "bias": bias,
               "rows": state["rows"] + held, "dropped": state["dropped"] + use.dropped,
               "row_buffer_heights": state["row_buffer_heights"] + use.heights}
        if by_loss:
            new["aux_loss"] = state["aux_loss"] + use.balance
        if sparse:
            new.update(indexer_loss=state["indexer_loss"] + use.indexer_loss,
                       selected_pairs=state["selected_pairs"] + use.selected)
        return new, loss, held

    def lm_eval(params, bias, x_all, y_all, rows):
        logits, _, _ = forward(cfg, params, bias, x_all[rows])
        return token_loss(logits, y_all[rows])

    train_step.__name__, init.__name__ = "lm_train_step", "lm_init"
    by_mask, visits, heads, rotary = [], [], [], []
    engaged, latent = _use_attention_kernel(cfg.seq_len), "latent_attention" in cfg.layer_types
    columns = _core_columns(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim) if latent \
        else _core_columns(cfg.head_dim, cfg.head_dim)  # a head's columns are the configuration's; its layers' heads are not
    for mask in MASKS:
        window = cfg.sliding_window if mask == "window" else None
        layers = [i for i, kind in enumerate(cfg.layer_types)
                  if kind in ATTENTION_KINDS and (cfg.window_of(kind) is None) == (window is None)]
        if not layers:
            continue
        by_mask.append((mask, len(layers) if engaged else 0))
        heads.append((mask, tuple(cfg.heads_of(i) for i in layers)))
        rotary.append((mask, tuple(0 if cfg.positional_encoding == "none" else cfg.qk_rope_head_dim if latent
                                   else cfg.rotary_of(cfg.layer_types[i]) for i in layers)))
        if engaged:  # a head's visits are its layer's, by its group; a mask's are the mean over its layers' heads
            per_layer = [_kernel_visits(cfg.seq_len, window, columns, 1 if latent else cfg.heads_of(i) // cfg.num_key_value_heads)
                         for i in layers]
            means = {name: sum(v[name] * n for v, n in zip(per_layer, heads[-1][1])) / sum(heads[-1][1]) for name in per_layer[0]}
            visits.append((mask, tuple(sorted((name, int(n) if n.is_integer() else n) for name, n in means.items()))))
    linear = cfg.layer_types.count("linear_attention")
    by_kernels, inverse_products = 0, 0.0
    if linear:
        per_key = cfg.linear_num_value_heads // cfg.linear_num_key_heads
        if _use_delta_kernel(cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.delta_chunk, per_key):
            from . import delta_kernel
            by_kernels, inverse_products = linear, delta_kernel.inverse_products(cfg.delta_chunk, per_key)
    state_space = cfg.layer_types.count("mamba2")
    sparse_program, sparse_kernel_visits = SPARSE_CORE_PROGRAMS[0], ()
    if sparse and _use_sparse_kernel(cfg.seq_len, cfg.heads_of(cfg.sparse_layers[0]) // cfg.num_key_value_heads, cfg.head_dim,
                                     cfg.attn_block):
        from . import sparse_kernel
        sparse_program = SPARSE_CORE_PROGRAMS[1]
        sparse_kernel_visits = tuple(sorted(sparse_kernel.visits(cfg.seq_len, _SPARSE_KERNEL_TILE).items()))
    return Lfm2MoePrograms(cfg, jax.jit(init), jax.jit(train_step, donate_argnums=0), jax.jit(lm_eval),
                           sum(n for _, n in by_mask), tuple(by_mask), tuple(visits),
                           ((LINEAR_CORE_PROGRAMS[0], linear),) if linear else (), tuple(heads), tuple(rotary),
                           by_kernels, inverse_products,
                           ((STATE_SPACE_CORE_PROGRAMS[0], state_space),) if state_space else (),
                           ((sparse_program, sparse),) if sparse else (),
                           tuple(sorted(_sparse_visits(cfg.seq_len, cfg.attn_block).items())) if sparse else (),
                           sparse_kernel_visits)


# -- configuration, data ------------------------------------------------------------------------------


def _normalize_config(x_train, config: Mapping[str, Any]) -> Tuple[Lfm2MoeConfig, int, Any]:
    """(the programs' static configuration, seed, cache_dir) from the keyword soup."""
    config = dict(config)
    seed, cache_dir = int(config.pop("seed", 0) or 0), config.pop("cache_dir", None)
    x = np.asarray(x_train)
    if x.ndim != 2 or not np.issubdtype(x.dtype, np.integer):
        raise ValueError(f"x_train must be integer tokens (sequences, length); got {x.dtype} {x.shape}")
    for key in ("layer_types", "layer_ids", "held_experts", "num_attention_heads_per_layer", "held_mamba_heads",
                "mrope_section"):
        if config.get(key) is not None:
            config[key] = tuple(config[key])
    if isinstance(config.get("rope_scaling"), Mapping):
        config["rope_scaling"] = tuple(sorted(config["rope_scaling"].items()))
    if isinstance(config.get("rope_parameters"), Mapping):
        config["rope_parameters"] = tuple(sorted((kind, tuple(sorted(block.items())))
                                                 for kind, block in config["rope_parameters"].items()))
    config.setdefault("layer_ids", tuple(range(len(config.get("layer_types", Lfm2MoeConfig.layer_types)))))
    cfg = Lfm2MoeConfig(**{**config, "seq_len": x.shape[1], "n_sequences": x.shape[0]})
    if len(cfg.layer_ids) != len(cfg.layer_types) or set(cfg.layer_types) - set(LAYER_KINDS):
        raise ValueError(f"layer_types {cfg.layer_types} (each one of {LAYER_KINDS}) / layer_ids {cfg.layer_ids}")
    if "latent_attention" in cfg.layer_types:
        sizes = {k: getattr(cfg, k) for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}
        if min(sizes.values()) <= 0 or cfg.qk_rope_head_dim % 2:
            raise ValueError(f"a latent_attention layer needs its rank and head sizes (an even rope size): {sizes}")
        wanted = {"factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim", "original_max_position_embeddings"}
        if cfg.yarn is not None and not wanted <= set(cfg.yarn):
            raise ValueError(f"rope_scaling needs {sorted(wanted)} (YaRN); got {sorted(cfg.yarn)}")
    if "linear_attention" in cfg.layer_types:
        sizes = {k: getattr(cfg, k) for k in ("linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                                              "linear_value_head_dim", "linear_conv_kernel_dim", "delta_chunk")}
        if min(sizes.values()) <= 0 or cfg.linear_num_value_heads % cfg.linear_num_key_heads:
            raise ValueError(f"a linear_attention layer needs its heads (value heads a whole number to each key "
                             f"head), their sizes, its taps and its chunk: {sizes}")
    if "mamba2" in cfg.layer_types:
        sizes = {k: getattr(cfg, k) for k in ("mamba_num_heads", "mamba_head_dim", "mamba_n_groups", "ssm_state_size",
                                              "mamba_conv_kernel", "mamba_chunk")}
        if min(sizes.values()) <= 0 or cfg.mamba_num_heads % cfg.mamba_n_groups:
            raise ValueError(f"a mamba2 layer needs its heads (a whole number to each group), their sizes, its taps "
                             f"and its chunk: {sizes}")
        (first, last), per_group = cfg.mamba_heads, cfg.mamba_num_heads // cfg.mamba_n_groups
        if not 0 <= first < last <= cfg.mamba_num_heads or first % per_group or last % per_group:
            raise ValueError(f"held_mamba_heads {cfg.held_mamba_heads} is no range of whole groups ({per_group} heads "
                             f"each, with their one B and C) of the {cfg.mamba_num_heads} heads")
    if cfg.sparse_layers:
        sizes = {k: getattr(cfg, k) for k in ("indexer_num_heads", "indexer_head_dim", "sparse_topk")}
        if min(sizes.values()) <= 0 or cfg.indexer_head_dim % 2:
            raise ValueError(f"a sparse_attention layer needs its indexer's heads, their (even) size and the keys it "
                             f"keeps a query: {sizes}")
        if cfg.num_dense_layers or cfg.single_half_layers:
            raise ValueError("a sparse_attention layer reports its indexer's loss through its routed feed-forward: "
                             "such a model has no dense layer and no layer that is one half")
    if cfg.mrope_section is not None and (min(cfg.mrope_section, default=0) <= 0
                                          or 2 * sum(cfg.mrope_section) != cfg.rotary_of("sparse_attention")):
        raise ValueError(f"mrope_section {cfg.mrope_section} must share out the {cfg.rotary_of('sparse_attention') // 2} "
                         f"pairs of columns that rope turns")
    if cfg.mlp_hidden_act not in ("silu", "relu2") or cfg.positional_encoding not in ("rope", "none"):
        raise ValueError(f"mlp_hidden_act {cfg.mlp_hidden_act!r} (silu: a SwiGLU; relu2: two matrices, no gate) / "
                         f"positional_encoding {cfg.positional_encoding!r} (rope or none)")
    if cfg.moe_latent_size < 0 or (cfg.moe_latent_size and cfg.gated_experts):
        raise ValueError(f"moe_latent_size {cfg.moe_latent_size}: the latent expert layer is built for experts of two "
                         f"matrices (mlp_hidden_act relu2), not for a gated expert ({cfg.mlp_hidden_act})")
    if cfg.single_half_layers and cfg.num_dense_layers:
        raise ValueError("a model whose layers are a mixer alone or a routed feed-forward alone has no dense layer")
    if not 0.0 < cfg.partial_rotary_factor <= 1.0:
        raise ValueError(f"partial_rotary_factor {cfg.partial_rotary_factor} is no share of a head")
    if cfg.shared_expert_gate and not cfg.n_shared_experts:
        raise ValueError("shared_expert_gate needs a shared expert to gate")
    if "sliding_attention" in cfg.layer_types and cfg.sliding_window <= 0:
        raise ValueError(f"a sliding_attention layer needs its sliding_window; got {cfg.sliding_window}")
    for kind, block in cfg.rope_parameters or ():
        block, yarn = dict(block), {"factor", "beta_fast", "beta_slow", "original_max_position_embeddings"}
        if kind not in ATTENTION_KINDS or "rope_theta" not in block or block.get("rope_type", "default") not in \
                ("default", "yarn") or (block.get("rope_type") == "yarn" and not yarn <= set(block)):
            raise ValueError(f"rope_parameters[{kind!r}] = {block}: a layer type of {ATTENTION_KINDS} with its "
                             f"rope_theta and a rope_type of default or yarn (yarn needs {sorted(yarn)})")
    per_layer = cfg.num_attention_heads_per_layer
    if per_layer is not None and len(per_layer) != len(cfg.layer_types):
        raise ValueError(f"num_attention_heads_per_layer {per_layer} against {len(cfg.layer_types)} layer_types")
    for i, kind in enumerate(cfg.layer_types):  # each attention layer by its own type's rope and its own heads
        if kind not in ("full_attention", "sliding_attention", "sparse_attention"):
            continue
        name, heads, rotary = f"layer {cfg.layer_ids[i]} ({kind})", cfg.heads_of(i), cfg.rotary_of(kind)
        if cfg.head_dim % 2 or heads <= 0 or heads % cfg.num_key_value_heads:
            raise ValueError(f"{name}: head_dim {cfg.head_dim} must be even and its {heads} heads a whole number of "
                             f"query heads to each of the {cfg.num_key_value_heads} key-value heads")
        if cfg.positional_encoding != "none" and (not 0 < rotary <= cfg.head_dim or rotary % 2):
            raise ValueError(f"{name}: rope turns {rotary} of a head's {cfg.head_dim} columns (partial_rotary_factor); "
                             f"an even number of them, at most all")
    if cfg.attn_output_gate and cfg.attn_head_gate:
        raise ValueError("attn_output_gate (a gate a column, inside W_q) and attn_head_gate (a gate a head, a "
                         "projection of its own) are two forms of one gate: a configuration has one")
    if cfg.n_shared_experts < 0 or cfg.shared_expert_intermediate_size < 0 or \
            (cfg.n_shared_experts and cfg.moe_intermediate_size <= 0):
        raise ValueError(f"{cfg.n_shared_experts} shared experts of width {cfg.moe_intermediate_size}")
    if cfg.scoring_func not in ("sigmoid", "softmax") or cfg.balance_rule not in _BALANCE_GENE:
        raise ValueError(f"scoring_func {cfg.scoring_func!r} (sigmoid or softmax) / balance_rule "
                         f"{cfg.balance_rule!r} (one of {sorted(_BALANCE_GENE)})")
    if not 0 <= cfg.num_dense_layers < len(cfg.layer_types):
        raise ValueError("num_dense_layers must leave at least one routed layer")
    if not 0 <= cfg.held_experts[0] < cfg.held_experts[1] <= cfg.num_experts:
        raise ValueError(f"held_experts {cfg.held_experts} is no range of the {cfg.num_experts} experts")
    if cfg.n_sequences < cfg.eval_sequences + cfg.batch_sequences or cfg.eval_sequences % cfg.batch_sequences:
        raise ValueError(f"{cfg.n_sequences} sequences cannot give {cfg.eval_sequences} held-out ones "
                         f"(whole batches of {cfg.batch_sequences}) and a train batch")
    if int(x.max(initial=0)) >= cfg.vocab_size or int(x.min(initial=0)) < 0:
        raise ValueError(f"token ids must lie in the held slice [0, {cfg.vocab_size})")
    return cfg, seed, cache_dir


def batch_plan(cfg: Lfm2MoeConfig, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(train rows (train_steps, batch_sequences), held-out rows (batches,
    batch_sequences)): the last ``eval_sequences`` are held out, the others are
    taken in an order drawn from ``seed``, again from the start if they run out."""
    n_train = cfg.n_sequences - cfg.eval_sequences
    order = np.random.default_rng([seed, 0xBA7C]).permutation(n_train)
    need = cfg.train_steps * cfg.batch_sequences
    train = np.resize(order, need).reshape(cfg.train_steps, cfg.batch_sequences)
    held_out = np.arange(n_train, cfg.n_sequences).reshape(-1, cfg.batch_sequences)
    return train.astype(np.int32), held_out.astype(np.int32)


def gene_names(balance_rule: str) -> Tuple[str, ...]:
    """The recipe's genes under ``balance_rule``, in the order the compiled programs take them."""
    return GENE_NAMES[:-1] + (_BALANCE_GENE[balance_rule],)


def gene_vector(genome: Mapping[str, Any], names: Optional[Sequence[str]] = None) -> np.ndarray:
    """The genome as the programs' float32 vector; without ``names`` the balance
    rule is read off the genome (whichever fifth gene it carries)."""
    if names is None:
        names = next(gene_names(rule) for rule, gene in _BALANCE_GENE.items() if gene in genome)
    return np.asarray([float(genome[name]) for name in names], np.float32)


class Lfm2MoeModel(GentunModel):
    """Train the LFM2-MoE share under a recipe genome; fitness = -mean validation loss.

    ``x_train`` holds token sequences (sequences, length), ``y_train`` the same
    shifted by one.  Keyword arguments are :class:`Lfm2MoeConfig`'s fields
    (``seq_len`` and ``n_sequences`` come from the data) plus ``seed`` and
    ``cache_dir`` (as in ``GeneticCnnModel``).
    """

    def __init__(self, x_train, y_train, genes: Mapping[str, Any], **config):
        super().__init__(x_train, y_train, genes)
        self.config = dict(config)

    def cross_validate(self) -> float:
        return float(self.cross_validate_population(self.x_train, self.y_train, [self.genes], **self.config)[0])

    @classmethod
    def compiled_programs(cls, x_train, **config) -> Lfm2MoePrograms:
        """The compiled init, train-step and eval callables of this
        configuration: the same lru-cached objects ``cross_validate_population``
        drives, so a caller that warms or checks them warms or checks the
        evaluator's own executables."""
        return _programs(_normalize_config(x_train, config)[0])

    @classmethod
    def cross_validate_population(cls, x_train, y_train, genomes: Sequence[Mapping[str, Any]], **config) -> np.ndarray:
        """Fitness of each genome, one after another on one compiled program.

        With telemetry on, one ``cv_call`` span whose children are, per
        individual, ``init_params``, ``train`` (all its steps, fenced once),
        ``eval`` and ``fetch`` (docs/OBSERVABILITY.md); off, the only wait is
        the fetch of each individual's losses, which is also what frees its
        state before the next one's is built."""
        with phase("cv_call", {"n_real": len(genomes), "pop": PROGRAM_WIDTH}):
            with phase("prepare"):
                cfg, seed, cache_dir = _normalize_config(x_train, config)
                if len(genomes) == 0:
                    return np.zeros((0,), np.float32)
                evaluation_prelude(cache_dir)
                _require_fit(cfg)
                programs = _programs(cfg)
                hashes = genome_hashes(genomes)
                init_base, _ = base_keys(seed)
                train_rows, val_rows = batch_plan(cfg, seed)
                x, y = jnp.asarray(x_train, jnp.int32), jnp.asarray(y_train, jnp.int32)
                train_rows, val_rows = jnp.asarray(train_rows), [jnp.asarray(r) for r in val_rows]
                steps = [np.int32(s) for s in range(cfg.train_steps)]
            fitness = np.empty(len(genomes), np.float32)
            for i, genome in enumerate(genomes):
                fitness[i] = -_score_one(programs, init_base, hashes[i], gene_vector(genome, cfg.gene_names), x, y,
                                         train_rows, val_rows, steps, i)
            return fitness


def _score_one(programs: Lfm2MoePrograms, init_base, genome_hash, genes, x, y, train_rows, val_rows, steps,
               individual: int) -> float:
    """Mean validation loss of one individual: fresh state, every train step,
    the held-out batches, one fetch."""
    cfg = programs.config
    shape = (cfg.tokens_per_step, cfg.train_steps)
    kernel_layer_steps = programs.attention_kernel_layers * cfg.train_steps
    by_mask = {mask: layers * cfg.train_steps for mask, layers in programs.kernel_layers_by_mask}
    kernel_attrs = {f"attention_kernel_layer_steps_{mask}": n for mask, n in by_mask.items()}
    for mask, visits in programs.kernel_visits:  # static: what the mask's kernel visits a layer, head and sequence
        kernel_attrs.update({f"attention_kernel_{name}_{mask}": n for name, n in visits})
    kernel_attrs.update({f"attention_heads_{mask}": list(heads) for mask, heads in programs.heads_by_mask})
    kernel_attrs.update({f"attention_rotary_columns_{mask}": list(columns) for mask, columns in programs.rotary_by_mask})
    by_linear = {program: layers * cfg.train_steps for program, layers in programs.linear_core_layers}
    kernel_attrs.update({f"linear_core_layer_steps_{program}": n for program, n in by_linear.items()})
    if by_linear:
        kernel_attrs["linear_core_chunk"] = cfg.delta_chunk
        kernel_attrs["linear_core_kernel_layer_steps"] = linear_kernel_steps = programs.linear_core_kernel_layers * cfg.train_steps
        kernel_attrs["linear_core_chain_products"] = (LINEAR_CORE_KERNEL_CHAIN_PRODUCTS if linear_kernel_steps
                                                      else LINEAR_CORE_CHAIN_PRODUCTS)
        kernel_attrs["linear_core_inverse_products"] = programs.linear_core_inverse_products
    by_state_space = {program: layers * cfg.train_steps for program, layers in programs.state_space_core_layers}
    kernel_attrs.update({f"state_space_core_layer_steps_{program}": n for program, n in by_state_space.items()})
    if by_state_space:
        kernel_attrs["state_space_core_chunk"] = cfg.mamba_chunk
        kernel_attrs["state_space_heads_held"] = cfg.mamba_held[0]
    if cfg.moe_latent_size:
        kernel_attrs["latent_experts_width"] = cfg.moe_latent_size
    by_sparse = {program: layers * cfg.train_steps for program, layers in programs.sparse_core_layers}
    if by_sparse:  # static: the layers x steps, the indexer's sizes, where the core is the fused kernels and what each form visits
        kernel_attrs.update(sparse_attention_layer_steps=sum(by_sparse.values()), sparse_topk=cfg.sparse_topk,
                            indexer_heads=cfg.indexer_num_heads,
                            sparse_core_kernel_layer_steps=by_sparse.get(SPARSE_CORE_PROGRAMS[1], 0),
                            **{f"sparse_core_{name}": n for name, n in programs.sparse_core_visits},
                            **{f"sparse_kernel_{name}": n for name, n in programs.sparse_kernel_visits})
    with phase("init_params", {"individual": individual}, program=(id(programs.init),)) as sp:
        state = sp.fence(programs.init(init_base, genome_hash))
        genes = jnp.asarray(genes)
    with phase("train", {"steps": cfg.train_steps, "tokens": cfg.train_steps * cfg.tokens_per_step,
                          "pop": PROGRAM_WIDTH, "individual": individual,
                          "attention_kernel_layer_steps": kernel_layer_steps, **kernel_attrs},
                program=(id(programs.train_step), shape)) as sp:
        for step in steps:
            state, _, _ = programs.train_step(state, x, y, train_rows, genes, step)
        sp.fence(state)
    with phase("eval", {"individual": individual, "tokens": len(val_rows) * cfg.tokens_per_step},
                program=(id(programs.eval), shape)) as sp:
        losses = sp.fence([programs.eval(state["params"], state["bias"], x, y, rows) for rows in val_rows])
    with phase("fetch", {"individual": individual}) as sp:
        if _tele.enabled():
            losses, rows, dropped, taken, balance, indexer_loss, selected = jax.device_get(
                (losses, state["rows"], state["dropped"], state["row_buffer_heights"], state.get("aux_loss"),
                 state.get("indexer_loss"), state.get("selected_pairs")))
            wide = int(RoutedStats(dropped, taken, balance).wide)
            by_height = list(zip(_row_buffer_heights(cfg, cfg.tokens_per_step), taken.tolist()))
            _count_expert_rows(cfg, rows, int(dropped), wide, by_height)
            for mask, n in by_mask.items():
                _get_registry().counter("attention_kernel_layer_steps_total", mask=mask).inc(n)
            for program, n in by_linear.items():
                _get_registry().counter("linear_core_layer_steps_total", program=program).inc(n)
            if by_linear:
                _get_registry().counter("linear_core_kernel_layer_steps_total").inc(linear_kernel_steps)
            for program, n in by_state_space.items():
                _get_registry().counter("state_space_core_layer_steps_total", program=program).inc(n)
            for program, n in by_sparse.items():
                _get_registry().counter("sparse_attention_layer_steps_total", program=program).inc(n)
            if by_sparse:  # the pairs a layer kept over the steps; the indexer's loss a layer and step
                sp.set(selected_pairs=selected.tolist(),
                       indexer_loss_mean=float(indexer_loss) / sum(by_sparse.values()))
            sp.set(expert_rows=rows.tolist(), dropped=int(dropped), wide_buffer=wide,
                   row_buffer_heights=[list(pair) for pair in by_height])
            if balance is not None:  # the ``aux_loss`` rule: the term before its weight, a routed layer and step
                balance = float(balance) / (len(cfg.moe_layers) * cfg.train_steps)
                _get_registry().counter("aux_loss_total").inc(balance)
                sp.set(aux_loss=balance)
        else:
            losses = jax.device_get(losses)
        del state  # the fetch has waited for the device: these 12 bytes a parameter are free again
    return float(np.mean(np.concatenate([np.ravel(l) for l in losses]), dtype=np.float64))


def _count_expert_rows(cfg: Lfm2MoeConfig, rows: np.ndarray, dropped: int, wide: int,
                       by_height: Sequence[Tuple[int, int]]) -> None:
    """``expert_rows{layer, expert}``, ``dropped_assignments_total``,
    ``row_buffer_wide_total`` and ``row_buffer_height_total{rows}`` from what an
    individual's steps summed on the device (telemetry on only)."""
    reg = _get_registry()
    for layer, per_expert in zip(cfg.moe_layers, rows):
        for expert, n in zip(range(*cfg.held_experts), per_expert):
            reg.counter("expert_rows", layer=str(cfg.layer_ids[layer]), expert=str(expert)).inc(int(n))
    reg.counter("dropped_assignments_total").inc(dropped)
    reg.counter("row_buffer_wide_total").inc(wide)
    for height, layer_steps in by_height:
        reg.counter("row_buffer_height_total", rows=str(height)).inc(layer_steps)
