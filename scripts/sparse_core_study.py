"""One ``sparse_attention`` layer's masked core alone, timed on the chip at the published shape (1 x 16,384 tokens, 4
key-value heads of 8 query heads of 128, each query keeping ~2,048 of its keys): the three Pallas kernels of
``gentun_tpu/models/sparse_kernel.py`` (forward, backward, the heads' share over every query block of 512) at the
tiles given, each beside the FLOPs of the tiles it visits.  No benchmark cell runs this: it is the instrument for the
next change to this class (PERF.md section 7).

    chiprun --chips 1 -- python scripts/sparse_core_study.py [--forward 256x512,512x1024] [--backward ...] [--share ...] [--check]

``--check`` also holds the kernels' ``out`` and gradients to the written-out masked softmax on the first 4,096
positions of one key-value head (float32 arithmetic on bfloat16 operands).  A line of JSON a result, on the output
and in ``chiprun_out/sparse_core_study.jsonl``.  On the CPU (``JAX_PLATFORMS=cpu``) it runs 4,096 positions of one
key-value head with the kernels interpreted and marks every line ``"rehearsal": true``: a check of the script, never a time.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp

from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.models import sparse_kernel as K

OUT = os.path.join(ROOT, "chiprun_out", "sparse_core_study.jsonl")
PEAK = 197e12  # bfloat16 FLOP/s of one TPU v5e chip (Google Cloud documentation, "TPU v5e")


def say(**line):
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as fh:
        fh.write(text + "\n")


def timed(fn, *args, n):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def operands(kv_heads, group, length, size, top, seed=0):
    """Normal q, k and v in bfloat16; a choice that keeps a key with probability top / (t + 1), and its own position always."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, kv_heads, group, length, size)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, kv_heads, length, size)).astype(jnp.bfloat16) for key in keys[1:3])
    t, s = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    kept = ((jax.random.uniform(keys[3], (length, length)) * (t + 1) < top) & (s <= t)) | (s == t)
    return q, k, v, kept[None]


def written_out(q, k, v, kept):
    scores = jnp.einsum("sngqd,snkd->sngqk", q, k, preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    prob = jax.nn.softmax(jnp.where(kept[:, None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("sngqk,snkd->sngqd", prob.astype(v.dtype), v, preferred_element_type=jnp.float32)


def main():
    ap = argparse.ArgumentParser()
    for kernel in ("forward", "backward", "share"):
        ap.add_argument(f"--{kernel}", default="%dx%d" % M._SPARSE_KERNEL_TILE, help="tiles to time, queries x keys, comma-separated; the shipped alone if not given")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args()
    chip = jax.default_backend() == "tpu"
    kv_heads, group, length, size, top, block = (4, 8, 16384, 128, 2048, 512) if chip else (1, 2, 4096, 128, 512, 512)
    mark = {} if chip else {"rehearsal": True}
    q, k, v, kept = jax.jit(operands, static_argnums=(0, 1, 2, 3, 4))(kv_heads, group, length, size, top)
    bits = jax.jit(K.packed)(kept)
    heads = kv_heads * group
    parse = lambda text: [tuple(int(n) for n in tile.split("x")) for tile in text.split(",")]
    dims = lambda tile=M._SPARSE_KERNEL_TILE: K.Dims(tile, size ** -0.5, M.SPARSE_KEPT[1:], not chip)

    def line(kernel, tile, ms, elements, products):
        flops = 2 * size * products * elements * heads
        say(kernel=kernel, tile=list(tile), ms=round(ms, 3), elements_a_head=elements, tflop=round(flops / 1e12, 3),
            share_of_peak=round(flops / PEAK / (ms / 1e3), 4) if chip else None, **mark)

    lse = None
    for tile in parse(args.forward):
        d = dims(tile)
        fn = jax.jit(lambda q, k, v, bits: K.core(q, k, v, bits, d))
        try:
            line("forward", tile, timed(fn, q, k, v, bits, n=args.calls), K.visits(length, tile)["elements"], 2)
            lse = fn(q, k, v, bits)[1]
        except Exception as e:  # a tile the chip's compiler refuses is a result too
            say(kernel="forward", tile=list(tile), error=str(e)[:400], **mark)
    for tile in parse(args.backward):
        d = dims(tile)
        both = jax.jit(jax.grad(lambda q, k, v, bits: jnp.sum(K.core(q, k, v, bits, d)[0].astype(jnp.float32)), argnums=(0, 1, 2)))
        alone = jax.jit(lambda q, k, v, bits: K.core(q, k, v, bits, d))
        try:
            ms = timed(both, q, k, v, bits, n=args.calls) - timed(alone, q, k, v, bits, n=args.calls)
            line("backward", tile, ms, K.visits(length, tile)["elements_bwd"], 5)
        except Exception as e:
            say(kernel="backward", tile=list(tile), error=str(e)[:400], **mark)
    for tile in parse(args.share):
        d = dims(tile)
        reach = lambda first: min((first // (4 * block) + 1) * 4 * block, length)  # the loss pass's groups of four blocks

        def every_block(q, k, lse, bits):
            return sum(jnp.sum(K.heads_share(q, k, lse, bits, jnp.int32(first), block, reach(first), d)[:, :, :block])
                       for first in range(0, length, block))
        try:
            line("share", tile, timed(jax.jit(every_block), q, k, lse, bits, n=args.calls),
                 K.visits(length, tile)["elements"], 1)
        except Exception as e:
            say(kernel="share", tile=list(tile), error=str(e)[:400], **mark)
    if args.check:
        cut, d = 4096, dims()
        qc, kc, vc, keptc = q[:, :1, :, :cut], k[:, :1, :cut], v[:, :1, :cut], kept[:, :cut, :cut]
        weights = jax.random.normal(jax.random.PRNGKey(9), qc.shape)
        ours = lambda q, k, v: K.core(q, k, v, K.packed(keptc), d)[0].astype(jnp.float32)
        theirs = lambda q, k, v: written_out(q, k, v, keptc)
        gap = lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(b.astype(jnp.float32))))
        grads = [jax.jit(jax.grad(lambda q, k, v, f=f: jnp.sum(f(q, k, v) * weights), argnums=(0, 1, 2)))(qc, kc, vc) for f in (ours, theirs)]
        say(check="against the written-out masked softmax", out=gap(jax.jit(ours)(qc, kc, vc), jax.jit(theirs)(qc, kc, vc)),
            **{f"d{name}": gap(a, b) for name, a, b in zip("qkv", *grads)}, **mark)


if __name__ == "__main__":
    main()
