"""Multi-host (multi-controller) execution: one worker spanning a pod slice.

The north-star topology is a v5e-32 — an 8-host slice — where ONE logical
worker owns 32 chips (BASELINE config #4 "multi-host TPU-VM workers").
Under jax's multi-controller model that worker is N processes (one per
host) running the SAME program over a global device mesh; collectives ride
ICI between the hosts' chips, and only process 0 talks to the master's
broker over DCN.

This module is the thin, fully-public-API seam that makes the rest of the
framework multi-process-safe:

- :func:`initialize` — ``jax.distributed.initialize`` wrapper the worker
  CLI calls before any backend init;
- :func:`place` / :func:`place_tree` — put a host-replicated array onto a
  (possibly cross-process) ``NamedSharding``.  Single-process this is
  exactly ``jax.device_put``; multi-process it goes through
  ``jax.make_array_from_process_local_data``, which is the blessed way to
  assemble a global array when every host holds the full value (our data
  pipeline is deterministic per-seed, so every host *does* — SURVEY.md §1
  "workers own the training data");
- :func:`fetch` — the inverse: global (possibly non-addressable) device
  array → full numpy array on every process, via
  ``multihost_utils.process_allgather``;
- :func:`broadcast_payload` — ship one process's Python object (job
  payloads off the broker) to all processes as two fixed-shape collectives
  (length, then a padded byte buffer), so follower processes can run the
  same evaluation program the leader runs.

Design rule enforced here: every cross-process interaction goes through
jax collectives over the device fabric — there is NO side-channel
host networking between a worker's processes (the broker connection
belongs to process 0 alone).
"""

from __future__ import annotations

import json
import logging
import os
import socket
import threading
from typing import Any, Optional

import numpy as np

import jax

__all__ = [
    "initialize",
    "process_count",
    "process_index",
    "is_leader",
    "place",
    "place_tree",
    "fetch",
    "broadcast_payload",
    "start_leader_watchdog",
]

logger = logging.getLogger("gentun_tpu")

#: coordinator address recorded by :func:`initialize` — doubles as the
#: leader-liveness signal for :func:`start_leader_watchdog`.
_coordinator: Optional[str] = None


def initialize(
    coordinator: str,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join (or found) a multi-process jax cluster.

    Must run before anything initializes a jax backend; after it,
    ``jax.devices()`` is the GLOBAL device list and ``auto_mesh`` therefore
    builds pod-slice-wide meshes with no further changes.

    On TPU pods, ``num_processes``/``process_id`` may be ``None`` — jax
    infers them from the TPU metadata.  On CPU/GPU clusters they are
    required.
    """
    global _coordinator
    kwargs: dict = {"coordinator_address": coordinator}
    if num_processes is not None:
        kwargs["num_processes"] = int(num_processes)
    if process_id is not None:
        kwargs["process_id"] = int(process_id)
    jax.distributed.initialize(**kwargs)
    _coordinator = coordinator
    logger.info(
        "jax.distributed initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


def process_count() -> int:
    """Processes in the cluster (1 when jax.distributed was never initialized)."""
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_leader() -> bool:
    """True on the process that owns external I/O (broker connection, logs)."""
    return jax.process_index() == 0


def place(x: Any, sharding) -> jax.Array:
    """Host value → device array under ``sharding``, multi-process-safe.

    Requires the host value to be identical on every process (deterministic
    pipelines guarantee this); each process contributes exactly its
    addressable shards.  An array already laid out as ``sharding`` passes
    through untouched — callers can therefore re-place cached global arrays
    (e.g. the device-resident dataset) every generation for free.
    """
    if isinstance(x, jax.Array) and x.sharding.is_equivalent_to(sharding, x.ndim):
        return x
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        # np.asarray on a non-addressable global array raises an obscure
        # addressability error deep in jax (ADVICE r3); name the real
        # problem and the two valid exits instead.
        raise ValueError(
            f"place(): cannot re-place a non-fully-addressable global array "
            f"(sharded as {x.sharding}) under a different sharding "
            f"({sharding}); fetch() it to a host value first, or re-place "
            f"the original host value"
        )
    x = np.asarray(x)
    # global_shape == local shape tells jax every process holds the FULL
    # array; it slices out each process's addressable shards locally.
    return jax.make_array_from_process_local_data(sharding, x, x.shape)


def place_tree(tree: Any, sharding) -> Any:
    """:func:`place` over a pytree (one sharding for every leaf)."""
    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)
    return jax.tree.map(lambda leaf: place(leaf, sharding), tree)


def fetch(x: jax.Array) -> np.ndarray:
    """Global device array → full numpy value on every process.

    Single-process this is ``np.asarray``; multi-process it all-gathers the
    non-addressable shards first (every process gets the same full array,
    keeping the SPMD programs in lockstep).
    """
    if jax.process_count() == 1:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def start_leader_watchdog(
    interval: float = 2.0,
    grace: int = 3,
    _exit=os._exit,
) -> threading.Event:
    """Bounded follower exit when the leader process dies (VERDICT r3 item 8).

    A follower rank waiting in :func:`broadcast_payload` blocks inside a
    collective; a SIGKILLed leader can never send the shutdown sentinel, so
    without this the follower hangs until the distributed runtime's own
    (long, version-dependent) collective timeout.  The jax coordination
    service listens in process 0 — the same process as the worker leader —
    so its TCP port doubles as a leader-liveness signal that needs no new
    side channel.  A daemon thread probes it every ``interval`` seconds and
    hard-exits the process with code 17 after ``grace`` consecutive
    failures: worst-case exit bound ≈ ``grace × (interval + connect
    timeout)`` — about 10 s at the defaults.  ``os._exit`` (not
    ``sys.exit``) because the thread stuck in the collective would block a
    normal interpreter shutdown.

    Returns a stop event — set it once the clean shutdown sentinel arrives.
    No-op on the leader itself, and when ``jax.distributed`` was
    initialized outside :func:`initialize` (no recorded coordinator).
    """
    stop = threading.Event()
    if is_leader() or not _coordinator or ":" not in _coordinator:
        return stop
    host, port_s = _coordinator.rsplit(":", 1)
    port = int(port_s)
    rank = process_index()

    def _loop() -> None:
        misses = 0
        while not stop.wait(interval):
            try:
                with socket.create_connection((host, port), timeout=max(1.0, interval)):
                    pass
                misses = 0
            except OSError:
                misses += 1
                if misses >= grace and not stop.is_set():
                    logger.error(
                        "leader liveness probe failed %d times (coordinator %s "
                        "unreachable); follower rank %d exiting with code 17",
                        misses, _coordinator, rank,
                    )
                    _exit(17)
                    return  # unreachable with the real os._exit; ends fakes

    threading.Thread(target=_loop, name="gentun-leader-watchdog", daemon=True).start()
    return stop


def _bucket_bytes(n: int) -> int:
    """Fixed-shape buckets (powers of two ≥ 256) bound broadcast recompiles."""
    b = 256
    while b < n:
        b *= 2
    return b


def broadcast_payload(obj: Any = None) -> Any:
    """Ship process 0's JSON-serializable object to every process.

    Callers on process 0 pass the object; followers pass anything (ignored)
    and receive process 0's value.  Two collectives: a scalar length, then
    a padded uint8 buffer whose bucketed size all processes derive from the
    broadcast length — fixed shapes, so jax caches the compiled programs.
    """
    from jax.experimental import multihost_utils

    if jax.process_count() == 1:
        return obj
    if is_leader():
        data = json.dumps(obj).encode("utf-8")
    else:
        data = b""
    n = int(multihost_utils.broadcast_one_to_all(np.int64(len(data))))
    # int32 elements, one byte each: jaxlib's gloo CPU collectives mangle
    # sub-word dtypes (a uint8 broadcast comes back with every byte widened
    # to 4 — the backend strides the buffer as 32-bit words), and 4 bytes
    # per payload byte is nothing next to job-payload sizes.
    buf = np.zeros(_bucket_bytes(n), dtype=np.int32)
    if is_leader():
        buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    out = np.asarray(multihost_utils.broadcast_one_to_all(buf)).astype(np.uint8)
    return json.loads(bytes(out[:n]).decode("utf-8"))
