"""BASELINE config #1: Genetic CNN on MNIST, S=(3,5), 10 individuals.

Single-process, CPU-runnable (run under JAX_PLATFORMS=cpu to stay off the chip).
Mirrors the reference's MNIST example (gentun examples [PUB]); data loads
offline (sklearn digits upscaled, or real MNIST via GENTUN_TPU_DATA).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from gentun_tpu import GeneticAlgorithm, GeneticCnnIndividual, Population
from gentun_tpu.utils import Checkpointer
from gentun_tpu.utils.datasets import load_mnist


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--generations", type=int, default=5)
    ap.add_argument("--population", type=int, default=10)
    ap.add_argument("--kfold", type=int, default=3)
    ap.add_argument("--epochs", type=int, nargs="+", default=[3])
    ap.add_argument("--lr", type=float, nargs="+", default=[0.01])
    ap.add_argument("--n-images", type=int, default=None, help="subsample the dataset")
    ap.add_argument("--kernels", type=int, nargs="+", default=[20, 50],
                    help="filters per stage (smaller = faster smoke runs)")
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--dense-units", type=int, default=500)
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args(argv)

    if args.n_images is not None and args.n_images <= 0:
        raise SystemExit(f"--n-images must be positive, got {args.n_images}")
    x, y, meta = load_mnist(**({"n": args.n_images} if args.n_images is not None else {}))
    print(f"data: {meta['source']} ({len(x)} images)")

    pop = Population(
        GeneticCnnIndividual,
        x_train=x,
        y_train=y,
        size=args.population,
        seed=0,
        additional_parameters=dict(
            nodes=(3, 5),
            kernels_per_layer=tuple(args.kernels),
            kfold=args.kfold,
            epochs=tuple(args.epochs),
            learning_rate=tuple(args.lr),
            batch_size=args.batch_size,
            dense_units=args.dense_units,
            seed=0,
        ),
    )
    ga = GeneticAlgorithm(pop, seed=0)
    if args.checkpoint:
        ckpt = Checkpointer(args.checkpoint)
        if ckpt.resume(ga):
            print(f"resumed at generation {ga.generation}")
        ga.set_checkpointer(ckpt)
    best = ga.run(args.generations)
    print(f"best architecture: {best.get_genes()}")
    print(f"best fitness (mean val acc): {best.get_fitness():.4f}")


if __name__ == "__main__":
    main()
