"""The chip check and the bench refuse a CPU; the rehearsal runs end to end.

``chip_smoke.py`` is what every later PR runs first on the TPU; these pin
what a sandbox can check of it: without a TPU it fails and prints no result,
a child that dies mid-phase fails it, and the ``--rehearsal`` switch drives
the same phases at tiny shapes on the CPU without ever printing a pass.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
import __graft_entry__ as graft_entry  # noqa: E402

CHIP_SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(cache_dir) -> dict:
    """A sandbox like the driver's: CPU only, one device, cache placed."""
    env = {k: v for k, v in os.environ.items() if k != "GENTUN_TPU_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return env


def _children(pid: int) -> list:
    """Pids whose parent is ``pid`` (Linux /proc)."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as fh:
                # the field after the parenthesised command name's ')' + state
                ppid = int(fh.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        if ppid == pid:
            out.append(int(name))
    return out


class TestBenchRefusesWhatItCannotMeasure:
    def test_bench_exits_nonzero_on_a_cpu(self, capsys):
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code not in (0, None)
        assert capsys.readouterr().out == ""  # no record of any kind

    def test_unknown_device_kind_is_an_error(self):
        assert bench.peak_flops("TPU v5 lite") == 197e12
        with pytest.raises(ValueError, match="no published peak"):
            bench.peak_flops("TPU v9 imaginary")

    def test_a_failed_gate_exits_nonzero(self):
        bench.gate(True, "fine")
        with pytest.raises(SystemExit) as exc:
            bench.gate(False, "accuracy fell")
        assert exc.value.code not in (0, None)


class TestChipSmokeWithoutAChip:
    def test_fails_on_a_cpu_and_prints_no_result(self, tmp_path):
        proc = subprocess.run([sys.executable, CHIP_SMOKE], env=_env(tmp_path / "cache"),
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0
        lines = proc.stdout.strip().splitlines()
        assert lines[-1].startswith("chip_smoke: FAIL")
        assert not any(line.startswith("{") for line in lines)
        assert "no TPU" in proc.stderr

    def test_fails_alone_in_a_directory(self, tmp_path):
        shutil.copy(CHIP_SMOKE, tmp_path / "chip_smoke.py")
        env = _env(tmp_path / "cache")
        env.pop("PYTHONPATH", None)
        proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0
        assert proc.stdout.strip().splitlines()[-1].startswith("chip_smoke: FAIL")
        assert '"ok"' not in proc.stdout

    def test_a_child_that_dies_mid_phase_fails_the_run(self, tmp_path):
        proc = subprocess.Popen([sys.executable, CHIP_SMOKE, "--rehearsal"],
                                env=_env(tmp_path / "cache"), stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True)
        try:
            deadline = time.monotonic() + 120
            kids = []
            while not kids and time.monotonic() < deadline:
                assert proc.poll() is None, "chip_smoke ended before starting a phase"
                kids = _children(proc.pid)
                time.sleep(0.1)
            assert kids, "no phase child appeared"
            os.kill(kids[0], signal.SIGKILL)
            out, _ = proc.communicate(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        assert proc.returncode != 0
        assert out.strip().splitlines()[-1].startswith("chip_smoke: FAIL: local")
        assert "rehearsal_passed" not in out


class TestRehearsal:
    def test_rehearsal_passes_end_to_end_and_never_reports_a_pass(self, tmp_path):
        cache = tmp_path / "cache"
        proc = subprocess.run([sys.executable, CHIP_SMOKE, "--rehearsal"], env=_env(cache),
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
        assert [line.get("phase") for line in lines] == ["local", "distributed", None]
        assert all(line["rehearsal"] is True for line in lines)
        assert not any("ok" in line for line in lines)
        local, dist, verdict = lines
        assert verdict["rehearsal_passed"] is True
        assert verdict["device"]["platform"] == "cpu"
        assert local["failed"] == [] and dist["failed"] == []
        # the cache went where the environment put it, and the worker found
        # there what the local phase had compiled
        assert local["cache_dir"] == dist["cache_dir"] == str(cache)
        assert os.listdir(cache)
        assert local["cache_hits"] == 0 and local["cache_requests"] > 0
        assert dist["train_programs_found_in_cache"] >= 1
        assert local["repeat"]["xla_compiles"] == 0
        assert dist["jobs_requeued"] == dist["evaluate_retries"] == 0
        assert dist["n_chips"] == [1] * len(dist["n_chips"])
        assert dist["evaluated"] == local["evaluated"]


class TestDryrunMultichip:
    """Where ``dryrun_multichip`` runs is decided by what is there."""

    def test_runs_in_process_on_the_virtual_cpu_mesh(self, monkeypatch):
        # conftest: an explicit JAX_PLATFORMS=cpu with 8 virtual devices.
        ran = []
        monkeypatch.setattr(graft_entry, "_dryrun_body", ran.append)
        graft_entry.dryrun_multichip(4)
        assert ran == [4]

    def test_refuses_to_swap_backends(self, monkeypatch):
        # No TPU and no explicit JAX_PLATFORMS=cpu: raise, do not fall back.
        monkeypatch.setattr(graft_entry, "_dryrun_body", lambda n: pytest.fail("ran"))
        monkeypatch.setenv("JAX_PLATFORMS", "")
        with pytest.raises(RuntimeError, match="explicit JAX_PLATFORMS=cpu"):
            graft_entry.dryrun_multichip(4)
