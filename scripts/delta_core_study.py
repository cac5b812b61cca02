"""One delta layer's core alone, timed on the chip at the published shape (1 x 16,384 tokens, 16 key and 32 value
heads of 128, chunks of 64): XLA's ``_delta_core_xla`` beside the Pallas kernels (``delta_kernel.delta_core``), each
forward, forward + backward and *as the mixer runs it* under ``forward``'s checkpoint (forward, forward again,
backward), with how far the kernels' output and gradients lie from XLA's.  No benchmark cell runs this: it is the
instrument for the next change to this class (PERF.md section 7 (b)).

    chiprun --chips 1 -- python scripts/delta_core_study.py [--cores xla,kernel] [--steps 1,2,4,8] \\
        [--inverse 2+dense,4+dense,2,8] [--one-pass] [--profile]

``--steps`` also times the kernels at other counts of chunks a grid step; ``--inverse`` at other forms of a chunk's
triangular inverse than the shipped one: ``<rows>`` the diagonal blocks inverted in closed form on the vector unit
(``delta_kernel.CLOSED_ROWS``, 4 as shipped; 2 is PR 43's start), ``+dense`` every doubling level as two dense
products, none at its live rows alone (``2+dense`` is PR 43's form, ten products a chunk; ``4+dense`` ISSUE 44's
step 1 alone, ``2`` its step 2 alone);
``--one-pass`` adds to every form of the kernels its diagnosis at one bfloat16 pass a product; ``--profile`` prints
each core's instructions by self time from a profiler trace of the mixer's form (the kernels by name).  A line of JSON a result,
on the output and in ``chiprun_out/delta_core_study.jsonl``.  On the CPU (``JAX_PLATFORMS=cpu``) it runs a small
shape with the kernels interpreted and marks every line ``"rehearsal": true``: a check of the script, never a time.
"""
import argparse
import collections
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import jax
import jax.numpy as jnp
import numpy as np

from gentun_tpu.models import delta_kernel
from gentun_tpu.models import lfm2_moe as M

OUT = os.path.join(ROOT, "chiprun_out", "delta_core_study.jsonl")


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
    return round((time.perf_counter() - t0) / n * 1e3, 3)


def operands(length, key_heads, heads, dk, dv, seed=0):
    """Unit keys, queries over the root of the key size, decay rates on (0.01, 4) a head, beta a sigmoid of a normal."""
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True))
    q = unit(rng.normal(size=(1, length, key_heads, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(1, length, key_heads, dk)))
    v = rng.normal(size=(1, length, key_heads, heads, dv))
    g = -rng.uniform(0.01, 4.0, size=(key_heads, heads)) * np.log1p(np.exp(rng.normal(size=(1, length, key_heads, heads))))
    beta = 1 / (1 + np.exp(-rng.normal(size=(1, length, key_heads, heads))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def forms(core):
    """The three programs a core is timed as."""
    def as_mixer(*a):  # the train step's: the forward, then under the checkpoint the forward again and the backward
        out, pull = jax.vjp(jax.checkpoint(core), *a)
        return pull(2.0 * out)

    return (jax.jit(core), jax.jit(jax.grad(lambda *a: (core(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4))), jax.jit(as_mixer))


def self_times(fn, args, calls=3):
    """Milliseconds a call of every device instruction of ``fn`` by its own time, from a profiler trace."""
    import trace_reduce
    directory = f"/tmp/delta_core_study_{os.getpid()}_{time.monotonic_ns()}"
    jax.block_until_ready(fn(*args))
    with jax.profiler.trace(directory):
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
    data = jax.profiler.ProfileData.from_file(trace_reduce.newest_xplane(directory))
    ops = [(e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9) for plane in data.planes
           if trace_reduce.DEVICE_PLANE.match(plane.name) for line in plane.lines if line.name == trace_reduce.OPS_LINE
           for e in line.events]
    total = collections.Counter()
    for name, seconds in trace_reduce.self_times(ops):
        total[trace_reduce.short_name(name).lstrip("%")] += seconds / calls * 1e3
    return {name: round(ms, 3) for name, ms in total.most_common(12)}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cores", default="xla,kernel")
    parser.add_argument("--steps", default="", help="counts of chunks a grid step to time the kernels at besides the rule's own")
    parser.add_argument("--inverse", default="", help="forms of the chunk's inverse to time the kernels at besides the shipped one: "
                        "<rows in closed form>[+dense], as 2+dense (PR 43's), 4+dense, 2, 8")
    parser.add_argument("--profile", action="store_true")
    parser.add_argument("--one-pass", action="store_true", help="also time the kernels' products at one bfloat16 pass (wrong results: a diagnosis)")
    parser.add_argument("--calls", type=int, default=10)
    cli = parser.parse_args()
    rehearsal = jax.default_backend() != "tpu"
    length, key_heads, heads, dk, dv, chunk = (200, 2, 2, 16, 24, 16) if rehearsal else (16384, 16, 2, 128, 128, 64)
    mark = {"rehearsal": True} if rehearsal else {}
    device = jax.devices()[0]
    say(device=device.device_kind, platform=device.platform, shape=[1, length, key_heads, heads, dk, dv], chunk=chunk, **mark)
    args = operands(length, key_heads, heads, dk, dv)
    @contextlib.contextmanager
    def patched(**values):
        """``delta_kernel.<name>`` at its value, each, while a form of the kernels is traced (the backward kernel is
        traced when jax transposes the program, after the core has returned: the whole first call runs inside)."""
        saved = {name: getattr(delta_kernel, name) for name in values}
        for name, value in values.items():
            setattr(delta_kernel, name, value)
        try:
            yield
        finally:
            for name, value in saved.items():
                setattr(delta_kernel, name, value)

    def kernel():  # a function of its own a form: jax keeps what it traced by the function's identity
        return lambda *a: delta_kernel.delta_core(*a, chunk, interpret=rehearsal)

    cores = {}  # name: (the core, what of ``delta_kernel`` it is traced with)
    if "xla" in cli.cores.split(","):
        cores["xla"] = (lambda *a: M._delta_core_xla(*a, chunk), {})
    variants = {"kernel": {}} if "kernel" in cli.cores.split(",") else {}
    for steps in (int(t) for t in cli.steps.split(",") if t):
        variants[f"kernel_steps_{steps}"] = dict(MAX_STEPS=steps)
    for form in (f for f in cli.inverse.split(",") if f):
        closed, _, dense = form.partition("+")
        variants[f"kernel_inverse_closed{closed}" + ("_dense" if dense else "")] = dict(
            CLOSED_ROWS=int(closed), **({"SUBLANES": 2 ** 30} if dense else {}))
    for name, values in variants.items():
        cores[name] = (kernel(), values)
        if cli.one_pass:  # a diagnosis, never a candidate: how much of the kernels' time the six passes of a float32 product are
            cores[name + "_one_bf16_pass_DIAGNOSIS"] = (kernel(), dict(values, _EXACT=dict(preferred_element_type=jnp.float32)))
    results = {}
    for name, (core, values) in cores.items():
        try:
            with patched(**values):
                fwd, both, mixer = forms(core)
                results[name] = (np.asarray(fwd(*args)), [np.asarray(x) for x in both(*args)])
                jax.block_until_ready(mixer(*args))
                products = {} if name == "xla" else {"inverse_products": delta_kernel.inverse_products(chunk, heads)}
            say(core=name, **products, fwd_ms=timed(fwd, *args, n=cli.calls), fwd_bwd_ms=timed(both, *args, n=max(cli.calls // 2, 1)),
                fwd_remat_bwd_ms=timed(mixer, *args, n=max(cli.calls // 2, 1)), **mark)
            if cli.profile and not rehearsal:
                say(core=name, self_ms_fwd=self_times(fwd, args), self_ms_mixer=self_times(mixer, args))
        except Exception as e:  # a form the compiler refuses is a result of the study
            say(core=name, error=str(e)[-600:], **mark)
    if "xla" in results:
        gap = lambda x, y: float(np.abs(x - y).max() / np.abs(y).max())
        for name, (out, grads) in results.items():
            if name != "xla":
                say(core=name, out_gap=gap(out, results["xla"][0]),
                    grad_gaps=dict(zip(("q", "k", "v", "g", "beta"), (gap(x, y) for x, y in zip(grads, results["xla"][1])))), **mark)


if __name__ == "__main__":
    main()
