"""Persistent XLA compilation cache tests (SURVEY.md §7 hard part #1).

The claims under test: the cache lives where ``JAX_COMPILATION_CACHE_DIR``
puts it, else in one fixed directory inside the checkout; and a *second
process* running the same search config reuses the on-disk compiled program
instead of recompiling.  Each run happens in a fresh subprocess (so no
in-process jit cache can help), pinned to a single CPU device for
byte-identical cache keys.
"""

import json
import os
import subprocess
import sys
import textwrap

from gentun_tpu.utils.xla_cache import (
    cache_stats,
    default_cache_dir,
    enable_compilation_cache,
    list_cache_entries,
    read_oom_cap,
    resolved_cache_dir,
    write_oom_cap,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One tiny CV run that asks for its own cache directory (argv[1]) and says
# where jax's persistent-cache config pointed before and after.
RUN_CV = textwrap.dedent(
    """
    import json, sys
    import numpy as np
    import jax

    before = jax.config.jax_compilation_cache_dir
    from gentun_tpu.models.cnn import GeneticCnnModel

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 2, size=64).astype(np.int32)
    accs = GeneticCnnModel.cross_validate_population(
        x, y, [{"S_1": (1, 0, 1)}],
        nodes=(3,), kernels_per_layer=(4,), kfold=2, epochs=(1,),
        learning_rate=(0.05,), batch_size=16, dense_units=8,
        compute_dtype="float32", seed=0, cache_dir=sys.argv[1],
    )
    print(json.dumps({"acc": float(accs[0]), "before": before,
                      "after": jax.config.jax_compilation_cache_dir}))
    """
)

PRINT_DEFAULT = (
    "from gentun_tpu.utils.xla_cache import default_cache_dir; "
    "print(default_cache_dir())"
)


def _subprocess_env(home, env_cache_dir=None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("GENTUN_TPU_CACHE_DIR", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    # ONE device: the tests assert cache hits, and the cache key includes the
    # device topology, so both runs must see identical topology.
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["HOME"] = str(home)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if env_cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_cache_dir)
    return env


def _run(code: str, env: dict, cwd: str, *argv: str) -> str:
    proc = subprocess.run(
        [sys.executable, "-c", code, *argv],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


class TestCachePlacement:
    """Where the persistent cache lives (ISSUE 21): the environment places
    it; otherwise one fixed directory inside the checkout."""

    def test_env_dir_takes_every_entry_and_second_process_adds_none(self, tmp_path):
        placed, asked, home = tmp_path / "placed", tmp_path / "asked", tmp_path / "home"
        home.mkdir()
        env = _subprocess_env(home, env_cache_dir=placed)

        first = json.loads(_run(RUN_CV, env, REPO, str(asked)))
        # jax read the variable itself and nothing re-pointed it, not even
        # the cache_dir= this call passed.
        assert first["before"] == first["after"] == str(placed)
        entries = sorted(os.listdir(placed))
        assert entries, "first run wrote no cache entries"
        assert not asked.exists()
        assert not (home / ".cache").exists()

        second = json.loads(_run(RUN_CV, env, REPO, str(asked)))
        assert second["acc"] == first["acc"]
        # All compiles hit the persistent cache: no new entries were written.
        assert sorted(os.listdir(placed)) == entries

    def test_unset_env_means_one_fixed_dir_inside_the_checkout(self, tmp_path):
        dirs = []
        for name in ("a", "b"):
            home = tmp_path / name
            home.mkdir()
            env = _subprocess_env(home)
            env["TMPDIR"] = str(home)
            dirs.append(_run(PRINT_DEFAULT, env, str(home)))
        assert dirs[0] == dirs[1] == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()

    def test_default_cache_dir_rule(self, monkeypatch):
        monkeypatch.delenv("GENTUN_TPU_CACHE_DIR", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert default_cache_dir() == os.path.join(REPO, ".jax_cache")
        # GENTUN_TPU_CACHE_DIR is a kill switch only: a path there places nothing.
        monkeypatch.setenv("GENTUN_TPU_CACHE_DIR", "/tmp/foo")
        assert default_cache_dir() == os.path.join(REPO, ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/placed")
        assert default_cache_dir() == "/tmp/placed"
        for off in ("0", "off", "NONE", "disabled"):
            monkeypatch.setenv("GENTUN_TPU_CACHE_DIR", off)
            assert default_cache_dir() is None

    def test_enable_never_repoints_a_cache_placed_from_outside(self, tmp_path, monkeypatch):
        import jax

        from gentun_tpu.utils import xla_cache

        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        monkeypatch.setattr(xla_cache, "_enabled_dir", None)
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, value: (updates.append(name), real_update(name, value)))
        assert xla_cache.enable_compilation_cache(str(tmp_path / "asked")) == placed
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_compile_time_secs" in updates
        assert not (tmp_path / "asked").exists()

    def test_enable_is_idempotent(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        d = str(tmp_path / "c")
        assert enable_compilation_cache(d) == enable_compilation_cache(d)


class TestEntryListing:
    """The helpers the compile service client builds its publish scans on
    (distributed/compile_service.py)."""

    def test_lists_regular_files_with_size_and_mtime(self, tmp_path):
        d = tmp_path / "cache"
        d.mkdir()
        (d / "entry_a").write_bytes(b"x" * 10)
        (d / "entry_b").write_bytes(b"y" * 20)
        (d / ".fetch-123.tmp").write_bytes(b"torn")  # in-flight write
        (d / "subdir").mkdir()
        entries = list_cache_entries(str(d))
        assert set(entries) == {"entry_a", "entry_b"}
        size, mtime = entries["entry_a"]
        assert size == 10 and mtime > 0

    def test_the_learned_caps_file_is_no_entry(self, tmp_path):
        """``.oom_caps.json`` lives beside the executables and is never
        listed, counted or shipped as one."""
        d = tmp_path / "cache"
        d.mkdir()
        (d / "entry_a").write_bytes(b"x" * 10)
        write_oom_cap(str(d), "a key", 16)
        write_oom_cap(str(d), "another", 8)
        assert sorted(os.listdir(d)) == [".oom_caps.json", "entry_a"]  # no temporary left behind
        assert (read_oom_cap(str(d), "a key"), read_oom_cap(str(d), "another"), read_oom_cap(str(d), "none")) == (16, 8, None)
        assert set(list_cache_entries(str(d))) == {"entry_a"} and cache_stats(str(d))["entries"] == 1

    def test_a_cap_is_keyed_by_device_and_compiler(self, monkeypatch):
        """``oom_cap_key``: one canonical string (no salted ``hash``) of the
        evaluator's key, the mesh and what the backend says of itself; None
        where a cap must not outlive the process."""
        import jax

        from gentun_tpu.utils import xla_cache

        class Client:
            platform_version = "libtpu built for the test"

        class Device:
            device_kind, client = "TPU v5 lite", Client()

            def __init__(self, stats):
                self.memory_stats = lambda: stats

        facts = xla_cache._device_facts.__wrapped__
        monkeypatch.setattr(xla_cache, "_device_facts", facts)
        monkeypatch.setattr(jax, "local_devices", lambda: [Device({"bytes_limit": 1 << 34})])
        key = xla_cache.oom_cap_key(((5, 5), "bfloat16", None), (1, 1))
        assert json.loads(key) == {
            "config": [[5, 5], "bfloat16", None], "mesh": [1, 1], "device_kind": "TPU v5 lite", "bytes_limit": 1 << 34,
            "local_devices": jax.local_device_count(), "jax": jax.__version__,
            "jaxlib": __import__("jaxlib").__version__, "platform_version": "libtpu built for the test"}
        assert key == xla_cache.oom_cap_key(((5, 5), "bfloat16", None), [1, 1]) and '"mesh":[1,1]' in key
        monkeypatch.setattr(jax, "process_count", lambda: 2)  # processes of one mesh must all chunk alike
        assert xla_cache.oom_cap_key((), (1, 1)) is None
        monkeypatch.setattr(jax, "process_count", lambda: 1)
        for stats in (None, {}, {"bytes_in_use": 5}):  # the CPU says None
            monkeypatch.setattr(jax, "local_devices", lambda stats=stats: [Device(stats)])
            assert xla_cache.oom_cap_key((), (1, 1)) is None

    def test_a_cap_that_cannot_be_written_is_a_warning(self, tmp_path, caplog):
        import logging

        with caplog.at_level(logging.WARNING, logger="gentun_tpu"):
            write_oom_cap(str(tmp_path / "nope"), "a key", 16)
        assert "could not keep the learned out-of-memory cap" in caplog.text
        assert not (tmp_path / "nope").exists()

    def test_missing_dir_is_empty_cache_not_error(self, tmp_path):
        assert list_cache_entries(str(tmp_path / "nope")) == {}

    def test_cache_stats_totals(self, tmp_path):
        d = tmp_path / "cache"
        d.mkdir()
        (d / "entry_a").write_bytes(b"x" * 10)
        (d / "entry_b").write_bytes(b"y" * 20)
        st = cache_stats(str(d))
        assert st["entries"] == 2
        assert st["bytes"] == 30
        assert st["dir"] == str(d)

    def test_disabled_cache_stats(self, monkeypatch):
        from gentun_tpu.utils import xla_cache

        monkeypatch.setattr(xla_cache, "_enabled_dir", None)
        monkeypatch.setenv("GENTUN_TPU_CACHE_DIR", "off")
        assert list_cache_entries() == {}
        assert cache_stats()["entries"] == 0


class TestCacheOptOutAndDegrade:
    def test_where_an_evaluation_caches(self, monkeypatch, tmp_path):
        """``resolved_cache_dir``: what ``evaluation_prelude`` enables and
        where a learned cap is kept; None is off."""
        from gentun_tpu.utils import xla_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("GENTUN_TPU_CACHE_DIR", "off")
        assert [resolved_cache_dir(v) for v in (None, False, "", "0", "off", " None ", "disabled")] == [None] * 7
        assert resolved_cache_dir("a/path") == os.path.abspath("a/path")  # a path given beats the kill switch
        monkeypatch.delenv("GENTUN_TPU_CACHE_DIR")
        assert resolved_cache_dir(None) == default_cache_dir()
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert resolved_cache_dir("a/path") == resolved_cache_dir(None) == str(tmp_path)  # the environment's beats both
        monkeypatch.setattr(xla_cache, "_failed_dirs", {str(tmp_path)})
        assert resolved_cache_dir(None) is None

    def test_unwritable_dir_degrades_with_warning(self, caplog, monkeypatch):
        import logging

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

        from gentun_tpu.utils import xla_cache

        with caplog.at_level(logging.WARNING, logger="gentun_tpu"):
            # Failure is distinguishable from success (ADVICE r4): None back.
            assert xla_cache.enable_compilation_cache("/proc/definitely/not/writable-x") is None
        # The warning names the actual outcome: DISABLED when nothing was
        # ever enabled, or the still-active previously-enabled dir (other
        # tests in this process may have enabled one).
        assert any(
            "caching DISABLED" in r.message or "previously-enabled" in r.message
            for r in caplog.records
        )

    def test_failed_dir_does_not_shadow_enabled_dir(self, tmp_path, monkeypatch):
        from gentun_tpu.utils import xla_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

        good = str(tmp_path / "good")
        assert xla_cache.enable_compilation_cache(good) == os.path.abspath(good)
        assert xla_cache.enable_compilation_cache("/proc/definitely/not/writable-y") is None
        # The enabled dir survives the failed call — and re-enabling it is
        # still recognized as already-active.
        assert xla_cache._enabled_dir == os.path.abspath(good)
        assert xla_cache.enable_compilation_cache(good) == os.path.abspath(good)

    def test_switching_dirs_resets_jax_cache_object(self, tmp_path, monkeypatch):
        """jax materializes its cache object lazily and keeps it forever;
        a dir switch must reset it or writes keep landing in the OLD dir."""
        from jax.experimental.compilation_cache import compilation_cache as cc

        from gentun_tpu.utils import xla_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = []
        monkeypatch.setattr(cc, "reset_cache", lambda: calls.append(1))
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        assert xla_cache.enable_compilation_cache(a) == os.path.abspath(a)
        n0 = len(calls)  # a previous test in this process may have switched
        assert xla_cache.enable_compilation_cache(a) == os.path.abspath(a)
        assert len(calls) == n0, "same-dir re-enable must not reset"
        assert xla_cache.enable_compilation_cache(b) == os.path.abspath(b)
        assert len(calls) == n0 + 1, "dir switch must reset jax's cache object"

    def test_cache_dir_false_is_programmatic_opt_out(self, monkeypatch):
        import jax
        import numpy as np

        from gentun_tpu.models.cnn import GeneticCnnModel

        monkeypatch.delenv("GENTUN_TPU_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        x = np.random.default_rng(0).normal(size=(32, 8, 8, 1)).astype(np.float32)
        y = np.zeros(32, np.int32)
        GeneticCnnModel.cross_validate_population(
            x, y, [{"S_1": (1, 0, 0)}], nodes=(3,), kernels_per_layer=(4,),
            dense_units=8, kfold=2, epochs=(1,), learning_rate=(0.01,),
            batch_size=16, seed=0, cache_dir=False,
        )
        assert jax.config.jax_compilation_cache_dir == before
