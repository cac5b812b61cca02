"""What every jax model family's evaluator shares, under none of them.

Three decisions that ``models/cnn.py``, ``models/lfm2_moe.py`` and the
benchmark's span readers all depend on:

- what a **phase span** is and when it is a ``compile`` (:func:`phase`);
- how a genome's **content becomes PRNG keys** (:func:`genome_hashes`,
  :func:`base_keys`, :func:`fold_content_keys`): what makes a fitness a pure
  function of (genome, config, seed);
- the **prelude** of an evaluation call (:func:`evaluation_prelude`): the
  persistent compile cache, the fleet's publish hooks, the backend mark.

A family imports from here and never from another family; this module imports
no family and no flax.
"""

from __future__ import annotations

import contextlib
import hashlib
import time
from typing import Any, Dict, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import spans as _tele
from ..telemetry.registry import get_registry as _get_registry
from ..utils.jax_state import mark_backend_used
from ..utils.xla_cache import (
    enable_compilation_cache,
    resolved_cache_dir,
    run_publish_hooks,
)

__all__ = ["phase", "genome_hashes", "base_keys", "fold_content_keys", "evaluation_prelude"]


#: Program shapes already executed once in this process — how the telemetry
#: split labels the FIRST call of a compiled shape `compile` and later calls
#: `train`/`eval`.  Keys are (callable id, shape signature); the callables
#: are lru-cached so ids are stable per static config.  "compile" honestly
#: means compile + first execution (jax offers no portable way to time the
#: compile alone without a throwaway AOT lower/compile cycle, which would
#: change the disabled-path behavior this module guarantees).
_seen_programs: set = set()


@contextlib.contextmanager
def _live_phase(kind: str, attrs: Dict[str, Any], program):
    """The telemetry-on half of :func:`phase`."""
    first = program is not None and program not in _seen_programs
    if first:
        attrs["phase"], kind = kind, "compile"
    # The annotation puts the span into the profiler's own trace, on the
    # profiler's clock, above the device ops it launched; scalars known at
    # entry ride along as its stats.
    with jax.profiler.TraceAnnotation(
        f"gentun/{kind}", **{k: v for k, v in attrs.items() if isinstance(v, (int, float, str))}
    ), _tele.span(kind, attrs) as sp:
        t0 = time.monotonic()
        try:
            yield sp
        except BaseException:
            if program is not None:
                # `compile`/`train`/`eval` stay what their readers take them
                # for, calls that returned: the deep configuration's 50-wide
                # attempt compiles for ~23 s and then runs out of memory.
                sp.kind = "call_failed"
            raise
        dur = time.monotonic() - t0
    if first:
        # First-compile latency histogram (docs/OBSERVABILITY.md): what a
        # compile-cache hit saves — compile + first execution, as the span.
        _seen_programs.add(program)
        _get_registry().histogram("compile_seconds").observe(dur)


def phase(kind: str, attrs: Optional[Dict[str, Any]] = None, program=None):
    """One named phase of an evaluation call (docs/OBSERVABILITY.md).

    Telemetry off: the spans module's shared no-op, nothing allocated and
    nothing synchronised.  On: a ``gentun/<kind>`` profiler annotation plus
    a span record.  A device call passes ``program`` (callable id + shape
    signature) and fences its result with ``sp.fence(...)``: the span's
    ``dispatch_s`` is how long the jitted call took to return, the rest of
    ``dur_s`` the wait for the device — jax dispatch is async, so an honest
    duration needs the block, and the block costs pipelining, which is why
    it happens ONLY when telemetry is on.  The first call of a program shape
    is labelled ``compile`` with the would-have-been kind as ``phase``.
    """
    if not _tele.enabled():
        return _tele.span(kind)
    return _live_phase(kind, dict(attrs) if attrs else {}, program)


def genome_hashes(genomes: Sequence[Mapping[str, Any]]) -> np.ndarray:
    """Stable per-genome 64-bit content hash, shape (n, 2) uint32, for PRNG keys.

    Folding each population slot's keys from the genome CONTENT instead of
    the slot index makes fitness a pure function of (architecture, config,
    seed): invariant to batch composition, slot order, compile-bucket
    padding, and OOM chunking (``cnn._chunked_by_cap``).  Without this, an
    architecture trained speculatively (``Population.speculative_fill``) or
    in a split chunk draws different init/dropout streams than the same
    architecture trained in its own generation's batch, so the cached
    fitness silently steers later selections — measured as a diverged
    search in the round-5 tailgen study.  (Cross-shape XLA recompilation
    can still reorder float reductions, but per-slot math is slot-local;
    in practice fitnesses now match bit-for-bit across batch shapes —
    asserted by ``tests/test_cnn_model.py::TestBatchCompositionPurity``.)

    blake2b(digest_size=8) rather than CRC32: two distinct architectures
    colliding share init/dropout streams, and a 31-bit space makes that
    a ~2% event at 10k genomes (birthday bound).  The 64-bit digest is
    split into (hi, lo) uint32 words, each folded into the key separately
    (:func:`fold_content_keys`), pushing collisions to ~3e-12 at the same
    scale.  Widening the hash changes every measured fitness value, hence
    ``FITNESS_PROTOCOL`` 3 (utils/fitness_store.py).
    """
    out = np.empty((len(genomes), 2), dtype=np.uint32)
    for i, g in enumerate(genomes):
        h = hashlib.blake2b(digest_size=8)
        for k in sorted(g):
            arr = np.asarray(g[k])
            arr = arr.astype(np.int64) if arr.dtype.kind in "biu" else arr.astype(np.float64)
            h.update(str(k).encode())
            h.update(str(arr.shape).encode())
            h.update(np.ascontiguousarray(arr).tobytes())
        digest = int.from_bytes(h.digest(), "little")
        out[i, 0] = digest >> 32  # hi word
        out[i, 1] = digest & 0xFFFFFFFF  # lo word
    return out


def fold_content_keys(base_key, f: int, hashes) -> jnp.ndarray:
    """(P, 2) PRNG keys of fold ``f``: the fold index, then the 64-bit genome
    content hash — as two uint32 words — folded into ``base_key``."""
    k = jax.random.fold_in(base_key, f)
    return jax.vmap(lambda hh: jax.random.fold_in(jax.random.fold_in(k, hh[0]), hh[1]))(hashes)


#: Keeps parameter-init streams disjoint from train (dropout) streams under
#: one seed.
_INIT_DOMAIN = 0x1217


def base_keys(seed: int, domain: int = 0):
    """``(init, train)`` base PRNG keys of one evaluation: the two streams'
    roots under ``seed``, both moved into ``domain`` when it is non-zero."""
    train = jax.random.PRNGKey(seed)
    init = jax.random.fold_in(train, _INIT_DOMAIN)
    if domain:
        init, train = jax.random.fold_in(init, domain), jax.random.fold_in(train, domain)
    return init, train


def evaluation_prelude(cache_dir) -> None:
    """What an evaluation call does before it touches a device.

    Persistent XLA compilation cache: a resumed/restarted search reuses
    the compiled program from disk (SURVEY.md §7 hard part #1).  ON by
    default; cache_dir=False (or "off"/"0"/"none") is the programmatic
    opt-out — None means "use the default" (utils/xla_cache.py).  A
    JAX_COMPILATION_CACHE_DIR in the environment beats any path given
    here: enable_compilation_cache never re-points a cache placed from
    outside.
    """
    cache_dir = resolved_cache_dir(cache_dir)
    if cache_dir:
        enable_compilation_cache(cache_dir)
    # Fleet-wide compile cache (distributed/compile_service.py): a worker
    # with a compile-cache client registered a hook here; this announces
    # "the previous evaluation may have been a first compile — scan and
    # publish what it wrote".  With no hooks (the default) this is one
    # empty-list iteration.
    run_publish_hooks()
    if _tele.enabled():
        # The host sampler (telemetry/sampler.py): ticks beside this call's
        # spans, until ``spans.disable()``.  Off: this one bool read.
        from ..telemetry import sampler

        sampler.ensure_started()

    # Everything after this touches devices; record that publicly so the
    # GA's per-chip metric can consult device counts without ever being the
    # thing that forces backend init (utils/jax_state).
    mark_backend_used()
