"""Multi-chip parallelism: mesh construction, input sharding, multi-host.

SURVEY.md §2.2: the reference has population-task parallelism only; the
rebuild adds per-worker data/population parallelism over a
``jax.sharding.Mesh``, with XLA inserting all collectives (GSPMD), and
multi-controller support so one worker can span a whole pod slice
(``multihost.py`` — BASELINE config #4 "multi-host TPU-VM workers").

``multihost`` is exposed lazily (PEP 562): it imports jax at module
level, and the dispatch plane (broker, master, worker re-chunking) must
be able to use the jax-free half of ``mesh.py`` — size-class
classification, ``mesh_factor``, ``host_worker_capacity`` — without
dragging a backend into the process.
"""

from .mesh import auto_mesh, mesh_axis_sizes, pad_population

__all__ = ["auto_mesh", "mesh_axis_sizes", "pad_population", "multihost"]


def __getattr__(name):
    if name == "multihost":
        from . import multihost

        return multihost
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
