"""Persistent XLA compilation cache (SURVEY.md §7 "hard parts" #1).

The masked-supergraph design already means one in-process compile serves the
whole search space (``models/cnn.py``), but a *restarted* search — the whole
point of the checkpoint/resume subsystem (``utils/checkpoint.py``) — would
pay the full XLA compile again.  jax ships a persistent on-disk compilation
cache; this module is the one place that manages it, so every entry point
(models, bench, examples) shares the same knob.

The cache is **ON by default**, and its place follows one rule
(:func:`default_cache_dir`):

- where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and
  that directory is the cache — nothing in this package re-points
  ``jax_compilation_cache_dir`` anywhere else, so whoever launches the
  program (a chip driver, an operator) decides where compiled programs
  survive;
- otherwise it is ``<checkout>/.jax_cache`` — one fixed, git-ignored
  directory beside the package, never derived from ``$HOME``, a temp name,
  a pid or the time, so two processes of one checkout always share it.

``GENTUN_TPU_CACHE_DIR=off`` (or ``0``/``none``/``disabled``) is the kill
switch (the test suite uses it), as is ``cache_dir=False`` on
``GeneticCnnModel`` / ``additional_parameters``.  It only disables; it is
not a second way to place the cache.  ``enable_compilation_cache("/path")``
enables a directory programmatically where the environment has not placed
one.

An unwritable cache directory degrades to caching disabled with a loud
warning — it must never take the training path down.

The thresholds are dropped to zero because GA fitness programs are small by
XLA standards: the default "only cache compiles > 1 s / > 0 bytes" heuristics
would skip exactly the programs we want cached.

Beside the compiled programs the directory holds one file of this package's
own, ``.oom_caps.json``: the population widths the evaluator's out-of-memory
healer learned (``models/cnn.py::_chunked_by_cap``), each under the
configuration, device and compiler it was learned on (:func:`oom_cap_key`).
A process that shares the directory starts at that width and does not pay
the attempt that found it; deleting the directory forgets the caps with
the programs.  With the cache off nothing is read and nothing is written.
"""

from __future__ import annotations

import functools
import json
import logging
import os
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

__all__ = [
    "cache_stats",
    "default_cache_dir",
    "enable_compilation_cache",
    "list_cache_entries",
    "oom_cap_key",
    "read_oom_cap",
    "register_publish_hook",
    "resolved_cache_dir",
    "run_publish_hooks",
    "unregister_publish_hook",
    "write_oom_cap",
]

logger = logging.getLogger("gentun_tpu")

_enabled_dir: Optional[str] = None
_failed_dirs: set = set()  # dirs that failed makedirs — don't retry/re-warn

#: The in-checkout default: the directory that holds the ``gentun_tpu``
#: package, i.e. the repository root of a checkout.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

# Publish hooks: the compile cache service client
# (``distributed/compile_service.py``) registers its scan-and-publish here
# so ``models/cnn.py`` can announce "a first compile may just have written
# an entry" without the models layer importing the distributed package
# (which would pull the broker stack into every model import).
_publish_hooks: list = []


def _env_cache_dir() -> Optional[str]:
    """``JAX_COMPILATION_CACHE_DIR`` as jax itself reads it, or None."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip() or None


def default_cache_dir() -> Optional[str]:
    """The persistent-cache directory, ON by default (opt out explicitly).

    ``GENTUN_TPU_CACHE_DIR`` set to ``0``/``off``/``none``/``disabled``
    returns None (caching is left alone).  Otherwise the directory is
    ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it, else the
    fixed ``<checkout>/.jax_cache``.  Measured on the chip in July 2026
    (DISTRIBUTED.md): a restarted search loaded a program from this cache
    in 15-25 s against 70-145 s to recompile it.
    """
    kill = os.environ.get("GENTUN_TPU_CACHE_DIR", "").strip().lower()
    if kill in ("0", "off", "none", "disabled"):
        return None
    return _env_cache_dir() or _CHECKOUT_CACHE_DIR


def _cache_path(cache_dir) -> str:
    """``cache_dir`` as one absolute path; ``JAX_COMPILATION_CACHE_DIR`` beats it."""
    return os.path.abspath(os.path.expanduser(str(_env_cache_dir() or cache_dir)))


def resolved_cache_dir(cache_dir) -> Optional[str]:
    """The directory an evaluation configured with ``cache_dir`` caches in.

    ``None`` means the default (:func:`default_cache_dir`); ``False`` or
    ``"off"``/``"0"``/``"none"`` is the programmatic opt-out.  As in
    :func:`enable_compilation_cache`, ``JAX_COMPILATION_CACHE_DIR`` beats a
    path given here.  Returns None where the cache is off or the directory
    has failed to enable.
    """
    if cache_dir is None:
        cache_dir = default_cache_dir()
    elif cache_dir is False or str(cache_dir).strip().lower() in ("", "0", "off", "none", "disabled"):
        cache_dir = None
    if not cache_dir:
        return None
    path = _cache_path(cache_dir)
    return None if path in _failed_dirs else path


def enable_compilation_cache(cache_dir: str) -> Optional[str]:
    """Point jax's persistent compilation cache at ``cache_dir``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory wins over
    the argument: jax read it at import, ``jax_compilation_cache_dir`` is
    left exactly as found, and only the thresholds below are applied.

    Idempotent; safe to call before or after jax backend init (the cache is
    consulted at compile time, not at backend-init time).  Returns the
    directory in use on success, or ``None`` when it could not be enabled
    (ADVICE r4: callers must be able to tell the difference — and a failed
    dir must not shadow a previously-enabled one, which stays active in jax).
    """
    global _enabled_dir
    env_dir = _env_cache_dir()
    cache_dir = _cache_path(cache_dir)
    if _enabled_dir == cache_dir:
        return cache_dir
    if cache_dir in _failed_dirs:
        return None
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        # On-by-default must not break read-only checkouts: degrade loudly.
        _failed_dirs.add(cache_dir)  # don't retry (and re-warn) every call
        if _enabled_dir is not None:
            logger.warning(
                "persistent XLA cache dir %s is unusable (%s); jax keeps "
                "caching at the previously-enabled %s", cache_dir, e, _enabled_dir,
            )
        else:
            logger.warning(
                "persistent XLA cache dir %s is unusable (%s); caching DISABLED "
                "— set JAX_COMPILATION_CACHE_DIR to a writable path, or "
                "GENTUN_TPU_CACHE_DIR=off to silence this", cache_dir, e,
            )
        return None
    import jax

    if env_dir is None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        if _enabled_dir is not None:
            # jax materializes its cache object lazily and keeps it for the
            # process lifetime: without a reset, writes keep landing in the
            # OLD dir even though the config now names the new one.
            from jax.experimental.compilation_cache import compilation_cache as _cc

            _cc.reset_cache()
    # GA fitness programs compile in well under the default 1 s threshold on
    # CPU test runs; cache everything.  The third knob makes cache keys
    # independent of the cache dir PATH: by default jax derives an
    # xla_gpu_per_fusion_autotune_cache_dir under the cache dir and hashes
    # that absolute path into every cache key, so two hosts (or two
    # checkouts) holding the cache at different paths could never reuse
    # each other's artifacts.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    _enabled_dir = cache_dir
    logger.info("persistent XLA compilation cache enabled at %s", cache_dir)
    return cache_dir


def list_cache_entries(cache_dir: Optional[str] = None) -> Dict[str, Tuple[int, float]]:
    """``{entry_name: (size_bytes, mtime)}`` for the cache directory.

    Entry names are jax's own cache-key hashes — they already encode the
    program, compile options and topology, which is what makes them valid
    content addresses for the compile service.  Dotfiles (in-flight
    ``.tmp`` writes) and subdirectories are skipped.  Defaults to the
    currently-enabled dir, falling back to :func:`default_cache_dir`.
    A missing directory is an empty cache, not an error.
    """
    d = cache_dir if cache_dir is not None else (_enabled_dir or default_cache_dir())
    if d is None:
        return {}
    out: Dict[str, Tuple[int, float]] = {}
    try:
        with os.scandir(d) as it:
            for entry in it:
                if entry.name.startswith("."):
                    continue
                try:
                    if not entry.is_file(follow_symlinks=False):
                        continue
                    st = entry.stat(follow_symlinks=False)
                except OSError:
                    continue
                out[entry.name] = (st.st_size, st.st_mtime)
    except FileNotFoundError:
        return {}
    return out


# -- learned out-of-memory caps ------------------------------------------------

#: The leading dot keeps the file out of :func:`list_cache_entries`, so the
#: compile service never ships it as an executable; jax's own LRU only
#: counts ``*-cache`` files.
_OOM_CAPS_FILE = ".oom_caps.json"

#: ``{directory: {key: cap}}`` as first read (or last written) by this
#: process: the file is read once a process and directory.
_oom_caps_read: Dict[str, Dict[str, int]] = {}


@functools.lru_cache(maxsize=None)
def _device_facts() -> Optional[Dict[str, Any]]:
    """What a cap depends on besides the configuration: the device, how much
    memory the backend gives it, how many there are, and the compiler
    (``platform_version`` is libtpu's build on a TPU; jax hashes the same
    string into its own cache keys).  None where a cap must not outlive the
    process: a backend that reports no memory limit (the CPU), and a mesh
    over several processes, which must all chunk alike and may not share a
    directory."""
    import jax
    import jaxlib

    if jax.process_count() > 1:
        return None
    device = jax.local_devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        return None
    return {
        "device_kind": device.device_kind,
        "bytes_limit": int(limit),
        "local_devices": jax.local_device_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform_version": device.client.platform_version,
    }


def oom_cap_key(config_key: Any, mesh_axes: Sequence[int]) -> Optional[str]:
    """The canonical string a learned cap is kept under, or None where it is
    not kept (:func:`_device_facts`).  ``config_key`` is the evaluator's own
    key (ints, strings and tuples of them), ``mesh_axes`` the ``(pop, data)``
    sizes: on a mesh the pop axis shards and the same configuration fits.
    Any difference is another key, so a miss."""
    facts = _device_facts()
    if facts is None:
        return None
    return json.dumps({"config": config_key, "mesh": list(mesh_axes), **facts},
                      sort_keys=True, separators=(",", ":"))


def _load_oom_caps(path: str, say: Callable[..., None] = logger.warning) -> Dict[str, int]:
    """The file's whole-number entries; absent or unreadable is empty."""
    try:
        with open(path, encoding="utf-8") as f:
            caps = json.load(f)["caps"]
        return {k: v for k, v in caps.items() if type(v) is int}
    except FileNotFoundError:
        return {}
    except (OSError, ValueError, KeyError, TypeError, AttributeError) as e:
        say("ignoring the learned out-of-memory caps in %s: %r", path, e)
        return {}


def read_oom_cap(cache_dir: str, key: str) -> Optional[int]:
    """The cap kept in ``cache_dir`` under ``key``, or None."""
    caps = _oom_caps_read.get(cache_dir)
    if caps is None:
        caps = _oom_caps_read[cache_dir] = _load_oom_caps(os.path.join(cache_dir, _OOM_CAPS_FILE))
    return caps.get(key)


def write_oom_cap(cache_dir: str, key: str, cap: int) -> None:
    """Keep ``cap`` under ``key``, beside what other processes have kept.

    Read again, merge, write to a temporary name, ``os.replace``: a reader
    never sees half a file.  Two processes that heal at once write the same
    value for the same key; a write lost between two keys costs some later
    process one more attempt.  Failing to write is a warning, never an error.
    """
    path = os.path.join(cache_dir, _OOM_CAPS_FILE)
    # quietly: the evaluator read the directory before the attempt that ends here, and
    # an unreadable file was called so then
    caps = _load_oom_caps(path, logger.debug)
    caps[key] = cap
    _oom_caps_read[cache_dir] = caps
    tmp = os.path.join(cache_dir, f".oom_caps-{os.getpid()}.tmp")
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"caps": caps}, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        logger.warning("could not keep the learned out-of-memory cap in %s: %r", path, e)


def cache_stats(cache_dir: Optional[str] = None) -> Dict[str, Any]:
    """Entry count + total bytes for ``/statusz``-style reporting."""
    d = cache_dir if cache_dir is not None else (_enabled_dir or default_cache_dir())
    entries = list_cache_entries(d)
    return {
        "dir": d,
        "enabled": _enabled_dir is not None and d == _enabled_dir,
        "entries": len(entries),
        "bytes": sum(size for size, _mtime in entries.values()),
    }


def register_publish_hook(fn: Callable[[], Any]) -> None:
    """Register a zero-arg callable to run after potential first compiles."""
    if fn not in _publish_hooks:
        _publish_hooks.append(fn)


def unregister_publish_hook(fn: Callable[[], Any]) -> None:
    _publish_hooks[:] = [h for h in _publish_hooks if h != fn]


def run_publish_hooks() -> None:
    """Run registered hooks; a failing hook never takes the caller down.

    Called from ``models/cnn.py::_prepare_population_setup`` right after
    the compile path runs — with no hooks registered this is one empty
    list iteration, so the default (service-less) configuration pays
    nothing.
    """
    for fn in list(_publish_hooks):
        try:
            fn()
        except Exception:  # noqa: BLE001 - hook boundary by design
            logger.warning("compile-cache publish hook %r failed", fn,
                           exc_info=True)
