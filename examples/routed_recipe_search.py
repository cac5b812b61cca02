"""Training-recipe search for the routed language-model family, at toy sizes on the CPU.

Every individual is the same architecture; the genome is how it is trained
(learning rate, warm-up, weight decay, beta2 and the balance rule's gene), and
a fitness is minus the validation loss after a few AdamW steps
(``gentun_tpu/models/lfm2_moe.py``).  The configuration alone says which
architecture runs: ``--arch lfm2`` is LFM2-24B-A2B's layer pattern (short
convolutions, GQA, sigmoid router with a bias rule; species ``lfm2-moe``),
``--arch deepseek-v2`` DeepSeek-V2-Lite's (latent attention, shared experts,
softmax router, balance loss; species ``deepseek-v2``), ``--arch mellum2``
Mellum2-12B-A2.5B-Instruct's (sliding-window and full attention 3:1, each
layer type with its own mask and rope, a stated head size, every layer routed,
weights normalised over the chosen; the same species: its genome is the
``aux_loss`` balance rule's).  The widths here are toys; the published widths
and their one-chip cut are ``benchmark/configs/lfm2_24b_a2b_ep8.json``,
``deepseek_v2_lite_ep8.json`` and ``mellum2_12b_a2p5b_ep8.json``.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from gentun_tpu import DeepseekV2Individual, GeneticAlgorithm, Lfm2MoeIndividual, Population

#: What both toy configurations share: 8 experts of which this "rank" holds 4, a vocabulary of 128.
_COMMON = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=48, num_dense_layers=1, num_experts=8,
               held_experts=(0, 4), vocab_size=128, batch_sequences=2, eval_sequences=2, attn_block=32,
               compute_dtype="float32")
ARCHITECTURES = {
    "lfm2": (Lfm2MoeIndividual, dict(
        _COMMON, layer_types=("conv", "full_attention", "conv"), num_experts_per_tok=2, num_attention_heads=2,
        num_key_value_heads=1)),
    "deepseek-v2": (DeepseekV2Individual, dict(
        _COMMON, layer_types=("latent_attention",) * 3, num_experts_per_tok=3, num_attention_heads=2, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
        rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=0.707,
                          original_max_position_embeddings=64),
        n_shared_experts=2, scoring_func="softmax", norm_topk_prob=False, balance_rule="aux_loss",
        tie_word_embeddings=False)),
    "mellum2": (DeepseekV2Individual, dict(
        _COMMON, num_dense_layers=0, num_experts_per_tok=3, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        layer_types=("sliding_attention",) * 3 + ("full_attention",), sliding_window=16, qk_norm=False,
        rope_parameters={"sliding_attention": dict(rope_type="default", rope_theta=500000),
                         "full_attention": dict(rope_type="yarn", rope_theta=500000, factor=16, beta_fast=32,
                                                beta_slow=1, original_max_position_embeddings=32,
                                                attention_factor=1.2772588722239782)},
        scoring_func="softmax", norm_topk_prob=True, balance_rule="aux_loss", tie_word_embeddings=False)),
}


def markov_tokens(n_sequences: int, length: int, vocab: int, seed: int) -> np.ndarray:
    """A chain that can be learned: half the steps follow a fixed successor, the others are Zipf draws."""
    rng = np.random.default_rng(seed)
    law = 1.0 / np.arange(1, vocab + 1) ** 0.8
    fresh = rng.choice(vocab, size=(n_sequences, length + 1), p=law / law.sum())
    follows, successor = rng.random((n_sequences, length + 1)) < 0.5, rng.permutation(vocab)
    tokens = fresh.copy()
    for t in range(1, length + 1):
        tokens[:, t] = np.where(follows[:, t], successor[tokens[:, t - 1]], fresh[:, t])
    return tokens.astype(np.int32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHITECTURES), default="deepseek-v2")
    ap.add_argument("--generations", type=int, default=3)
    ap.add_argument("--population", type=int, default=6)
    ap.add_argument("--train-steps", type=int, default=6)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--n-sequences", type=int, default=16)
    args = ap.parse_args(argv)

    species, config = ARCHITECTURES[args.arch]
    tokens = markov_tokens(args.n_sequences, args.seq_len, config["vocab_size"], seed=0)
    print(f"data: {args.n_sequences} synthetic sequences of {args.seq_len} tokens; species {species.__name__}")
    pop = Population(species, x_train=tokens[:, :-1], y_train=tokens[:, 1:], size=args.population, seed=0,
                     additional_parameters=dict(config, train_steps=args.train_steps, seed=0))
    best = GeneticAlgorithm(pop, seed=0).run(args.generations)
    print(f"best recipe: {best.get_genes()} (validation loss {-best.get_fitness():.4f})")


if __name__ == "__main__":
    main()
