"""Multi-host (multi-controller) tests: one worker spanning processes.

VERDICT r2 "do this" #1: the north-star topology is a v5e-32 — an 8-host
slice owned by ONE worker.  No multi-host TPU exists in CI, so these tests
form real 2- and 4-process jax clusters over CPU (8 global virtual devices
split across the processes — the same mechanism as ``conftest.py``) and
prove:

- the sharded population CV runs under multi-controller execution and
  matches the single-process result on the same logical mesh;
- the leader/follower worker loop (process 0 owns the broker connection,
  payload broadcast over the device fabric) completes real jobs end to end.

The children run in subprocesses (``_multihost_child.py``) because a jax
cluster needs one process per "host"; the parent uses its own in-process
8-device CPU backend for the single-process reference run.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_multihost_child.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_cluster(mode: str, out_path: str, extra_args=(), nproc: int = 2,
                   total_devices: int = 8):
    """Launch an nproc-process jax CPU cluster of _multihost_child.py.

    ``total_devices`` global devices split across nproc processes — the
    classic tests run 8 (the conftest mesh size; 2×4 mirrors "few hosts,
    several chips each"), the v5e-32-shape test runs 32 as 8×4.
    """
    coord_port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={total_devices // nproc}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, CHILD, mode, str(pid), str(nproc), str(coord_port), out_path,
             *map(str, extra_args)],
            env=env,
            cwd=REPO,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for pid in range(nproc)
    ]
    return procs


# Environment limitations (vs regressions): a cluster child that dies with
# one of these signatures means THIS interpreter/jaxlib/box cannot run the
# multi-process jax topology under test — skip with the precise reason,
# never fail-by-environment.  Any other child death is a real failure, and
# it wins over env signatures in peers: when one child hits a genuine bug,
# the survivors abort with gloo connection resets, so a skip is only valid
# if EVERY failed child shows an environment signature.
_ENV_SKIP_PATTERNS = (
    ("Multiprocess computations aren't implemented",
     "this jaxlib's CPU backend has no cross-process collectives "
     "implementation (jax_cpu_collectives_implementation/gloo unavailable)"),
    ("gloo::EnforceNotMet",
     "jaxlib's gloo CPU collectives crashed inside the cluster child"),
    ("external/gloo/gloo/transport/tcp",
     "jaxlib's gloo TCP collectives lost a peer mid-collective (abort "
     "cascade — seen with 8 ranks contending for this box's single CPU "
     "core)"),
    # The coordination-service flavor of the same cascade: a child that
    # never errored itself is torn down by jax.distributed because a
    # peer died ("another task died").  Harmless to recognize — a child
    # with a REAL bug dies with its own traceback, lacks this line, and
    # still wins over every peer's signature (see _resolve_failures).
    ("Terminating process because the JAX distributed service detected "
     "fatal errors",
     "jax coordination service tore this child down after a peer died "
     "(peer-abort cascade; the peers carried gloo environment "
     "signatures)"),
)


def _env_limit_reason(out: str):
    for needle, why in _ENV_SKIP_PATTERNS:
        if needle in out:
            return why
    return None


def _resolve_failures(failures):
    """``failures`` is ``[(rc, output), ...]`` for every child that died
    nonzero on its own.  Any failure WITHOUT an environment signature is a
    real regression and raises with that child's output; only when all of
    them carry one does the test skip."""
    reasons = []
    for rc, out in failures:
        why = _env_limit_reason(out)
        if why is None:
            raise AssertionError(f"cluster child died rc={rc}:\n{out[-3000:]}")
        reasons.append(why)
    if reasons:
        pytest.skip(f"multi-process jax unsupported in this environment: {reasons[0]}")


def _check_alive(procs):
    """While waiting on a cluster: a child already dead of an environment
    limitation skips the test immediately instead of timing the wait out;
    any other dead child fails it with the child's output."""
    if all(p.poll() is None or p.returncode == 0 for p in procs):
        return
    time.sleep(1.0)  # let peer-abort cascades land before sampling outputs
    killed = [p for p in procs if p.poll() is None]
    for p in killed:
        p.kill()
    failures = []
    for p in procs:
        out, _ = p.communicate()
        text = out.decode(errors="replace") if out else ""
        if p.returncode != 0 and p not in killed:
            failures.append((p.returncode, text))
    _resolve_failures(failures)


def _join(procs, timeout: float):
    deadline = time.monotonic() + timeout
    outs = []
    for p in procs:
        remaining = max(1.0, deadline - time.monotonic())
        try:
            out, _ = p.communicate(timeout=remaining)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out.decode(errors="replace"))
    _resolve_failures(
        [(p.returncode, out) for p, out in zip(procs, outs) if p.returncode != 0])
    return outs


@pytest.fixture(scope="module")
def single_process_reference():
    """The (2,4)-mesh single-process CV result, computed once per module —
    it is independent of how many processes the cluster splits into."""
    sys.path.insert(0, os.path.dirname(CHILD))
    try:
        from _multihost_child import run_cv
    finally:
        sys.path.pop(0)
    from gentun_tpu.parallel.mesh import auto_mesh

    mesh = auto_mesh(pop_axis=2, data_axis=4)
    if mesh is None:
        pytest.skip("single-process reference needs the 8-virtual-device "
                    "CPU environment (conftest XLA_FLAGS)")
    return np.asarray(run_cv(mesh), dtype=np.float32)


@pytest.mark.parametrize("nproc", [2, 4])
def test_cluster_cv_matches_single_process(tmp_path, nproc, single_process_reference):
    """nproc processes × (8/nproc) virtual CPU devices = one 8-device
    cluster running the REAL sharded CV path; the leader's accuracies must
    match this process's single-process run on the same logical (2, 4)
    mesh.  4 processes exercises the many-hosts shape of a pod slice."""
    want = single_process_reference
    out_path = str(tmp_path / "accs.json")
    procs = _spawn_cluster("cv", out_path, nproc=nproc)
    _join(procs, timeout=480.0)
    with open(out_path) as f:
        got = np.asarray(json.load(f), dtype=np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_cluster_cv_matches_single_process_v5e32_shape(tmp_path):
    """The NORTH-STAR topology's exact shape (VERDICT r4 item 3): 32 global
    devices on an (8, 4) pop×data mesh, as the v5e-32's 8 hosts × 4 chips.
    The 8-process cluster run must match a 1-process run over the same 32
    logical devices — same mesh factoring, same collective shapes, only the
    process boundaries differ."""
    ref_path = str(tmp_path / "ref.json")
    got_path = str(tmp_path / "got.json")
    # Reference first (1 process × 32 virtual devices): also a jax cluster,
    # just a trivial one, so the code path is identical end to end.
    _join(_spawn_cluster("cv32", ref_path, nproc=1, total_devices=32), timeout=480.0)
    _join(_spawn_cluster("cv32", got_path, nproc=8, total_devices=32), timeout=480.0)
    with open(ref_path) as f:
        want = np.asarray(json.load(f), dtype=np.float32)
    with open(got_path) as f:
        got = np.asarray(json.load(f), dtype=np.float32)
    assert want.shape == (8,)  # 8 genomes filled the 8-row population axis
    assert np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_multihost_worker_completes_jobs(tmp_path):
    """Full leader/follower worker: process 0 consumes from the broker,
    broadcasts batches over the device fabric, every rank evaluates, only
    the leader replies — and the master's barrier completes."""
    from gentun_tpu.distributed import JobBroker

    rng = np.random.default_rng(7)
    genomes = [
        {"S_1": [int(b) for b in rng.integers(0, 2, 6)],
         "S_2": [int(b) for b in rng.integers(0, 2, 6)]}
        for _ in range(4)
    ]
    payloads = {
        f"job-{i}": {"genes": g, "additional_parameters": {"nodes": (4, 4)}}
        for i, g in enumerate(genomes)
    }
    broker = JobBroker(port=0).start()
    procs = []
    try:
        _, port = broker.address
        out_path = str(tmp_path / "worker.json")
        procs = _spawn_cluster("worker", out_path, extra_args=(port, len(payloads)))
        deadline = time.monotonic() + 240.0
        while not broker._workers and time.monotonic() < deadline:
            _check_alive(procs)  # env-limited child death → skip, not timeout
            time.sleep(0.1)
        broker.submit(payloads)
        results = broker.gather(list(payloads), timeout=300.0)
        expected = {
            f"job-{i}": float(sum(sum(g) for g in genomes[i].values()))
            for i in range(len(genomes))
        }
        assert results == expected
        _join(procs, timeout=120.0)
        # Both ranks evaluated every job (lockstep), one rank replied.
        with open(out_path + ".rank0") as f:
            assert json.load(f)["jobs_done"] == len(payloads)
        with open(out_path + ".rank1") as f:
            assert json.load(f)["jobs_done"] == len(payloads)
    finally:
        for p in procs:  # never leak the cluster on a gather/assert failure
            if p.poll() is None:
                p.kill()
        broker.stop()


def test_follower_exits_bounded_when_leader_sigkilled(tmp_path):
    """VERDICT r3 item 8: SIGKILL the leader rank (no shutdown sentinel) —
    the follower must exit nonzero within a bounded time instead of hanging
    until the runtime's collective timeout.  Code 17 is the leader
    watchdog's signature (multihost.start_leader_watchdog); a fast
    collective-layer failure may occasionally beat the watchdog, which is
    an equally bounded nonzero exit."""
    from gentun_tpu.distributed import JobBroker

    broker = JobBroker(port=0).start()
    procs = []
    try:
        _, port = broker.address
        out_path = str(tmp_path / "wd.json")
        procs = _spawn_cluster("worker", out_path, extra_args=(port, 100))
        deadline = time.monotonic() + 240.0
        while not broker._workers and time.monotonic() < deadline:
            _check_alive(procs)
            time.sleep(0.1)
        assert broker._workers, "leader never connected to the broker"
        time.sleep(1.0)  # follower is in its broadcast loop, watchdog armed
        procs[0].kill()  # SIGKILL: the sentinel can never be sent
        t0 = time.monotonic()
        out, _ = procs[1].communicate(timeout=60.0)
        elapsed = time.monotonic() - t0
        assert procs[1].returncode not in (0, None), out.decode(errors="replace")[-2000:]
        assert elapsed < 45.0, f"follower took {elapsed:.1f}s to notice leader death"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        broker.stop()


def test_multihost_worker_real_cnn_matches_single_process(tmp_path):
    """VERDICT r3 item 4 — the v5e-32 worker's exact composition, end to
    end: master barrier → broker jobs → leader broadcast over the device
    fabric → ``Population.evaluate`` → sharded ``GeneticCnnModel`` CV
    across a 2-process cluster.  Fitnesses must match a single-process
    evaluation of the same genomes under the same (auto) mesh logic, and
    the worker must advertise the slice's full chip count."""
    sys.path.insert(0, os.path.dirname(CHILD))
    try:
        from _multihost_child import build_small_cnn_workload
    finally:
        sys.path.pop(0)
    from gentun_tpu import GeneticCnnIndividual, Population
    from gentun_tpu.distributed import JobBroker

    x, y, genomes, config = build_small_cnn_workload()
    # Share one persistent XLA cache between this process and the cluster
    # children so they can load what the reference run compiled instead of
    # recompiling under in-suite CPU contention.
    config = dict(config, cache_dir=str(tmp_path / "xla-cache"))
    # Reference = the SINGLE-PROCESS Population.evaluate path (exactly what
    # the worker runs): this includes the canonical-architecture dedup, so
    # an isomorphic pair in the genome set — deliberately present — must
    # share one fitness on both sides.
    ref_pop = Population(
        GeneticCnnIndividual,
        x_train=x,
        y_train=y,
        individual_list=[
            GeneticCnnIndividual(x_train=x, y_train=y, genes=g,
                                 additional_parameters=dict(config))
            for g in genomes
        ],
        additional_parameters=dict(config),
    )
    ref_pop.evaluate()
    want = np.asarray([ind.get_fitness() for ind in ref_pop], dtype=np.float32)

    payloads = {
        f"cnn-{i}": {
            "genes": {k: list(v) for k, v in g.items()},
            "additional_parameters": {
                k: (list(v) if isinstance(v, tuple) else v) for k, v in config.items()
            },
        }
        for i, g in enumerate(genomes)
    }
    # Long heartbeat: a contended compile can starve the leader's ping
    # thread past the 15 s default, and a spurious mid-compile reap turns
    # one slow evaluation into several.
    broker = JobBroker(port=0, heartbeat_timeout=300.0).start()
    procs = []
    try:
        _, port = broker.address
        out_path = str(tmp_path / "cnn_worker.json")
        procs = _spawn_cluster("worker-cnn", out_path, extra_args=(port, len(payloads)))
        # One logical worker spanning the whole 8-device slice advertises
        # all of it in its hello (VERDICT r3 item 3 on the real species);
        # check while it is connected — it disconnects after max_jobs.
        deadline = time.monotonic() + 600.0
        while broker.fleet_chips() != 8 and time.monotonic() < deadline:
            _check_alive(procs)
            time.sleep(0.2)
        assert broker.fleet_chips() == 8
        broker.submit(payloads)
        # Generous: suite runs share the host CPU with other XLA compiles.
        results = broker.gather(list(payloads), timeout=900.0)
        got = np.asarray([results[f"cnn-{i}"] for i in range(len(genomes))], dtype=np.float32)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        _join(procs, timeout=120.0)
        with open(out_path + ".rank1") as f:
            assert json.load(f)["jobs_done"] == len(payloads)  # lockstep rank
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        broker.stop()
