"""Tests for the masked-supergraph Genetic-CNN fitness model (models/cnn.py).

SURVEY.md §4: the rebuild must supply genome→module decode tests and
single-chip train-step correctness the reference never had.  Everything here
runs on the virtual CPU mesh (conftest pins jax to cpu).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gentun_tpu.models.cnn import GeneticCnnModel, MaskedGeneticCnn
from gentun_tpu.ops.dag import stack_genome_masks

FAST = dict(
    nodes=(3,),
    kernels_per_layer=(8,),
    kfold=2,
    epochs=(2,),
    learning_rate=(0.05,),
    batch_size=32,
    dense_units=32,
    compute_dtype="float32",
    seed=0,
)


@pytest.fixture(scope="module")
def separable_data():
    """4 classes of 8×8 images with distinct mean patterns — easy to learn."""
    rng = np.random.default_rng(0)
    protos = rng.normal(size=(4, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 4, size=192).astype(np.int32)
    x = protos[y] + 0.3 * rng.normal(size=(192, 8, 8, 1)).astype(np.float32)
    return x, y


def _masks_for(genes, nodes):
    return [
        {k: jnp.asarray(v[0]) for k, v in stage.items()}
        for stage in stack_genome_masks([genes], nodes)
    ]


class TestMaskedGeneticCnnForward:
    def test_output_shape_two_stages(self):
        model = MaskedGeneticCnn(
            nodes=(3, 5), filters=(4, 8), dense_units=16, n_classes=10,
            compute_dtype=jnp.float32,
        )
        genes = {"S_1": (1, 0, 1), "S_2": (1,) * 10}
        masks = _masks_for(genes, (3, 5))
        x = jnp.zeros((2, 16, 16, 1))
        params = model.init(jax.random.PRNGKey(0), x, masks)
        out = model.apply(params, x, masks)
        assert out.shape == (2, 10)
        assert out.dtype == jnp.float32

    def test_identity_stage_matches_entry_conv_passthrough(self):
        """All-zero genome ⇒ stage output is the entry conv output, pooled."""
        model = MaskedGeneticCnn(
            nodes=(3,), filters=(4,), dense_units=8, n_classes=2,
            compute_dtype=jnp.float32,
        )
        masks = _masks_for({"S_1": (0, 0, 0)}, (3,))
        x = jnp.ones((1, 8, 8, 1))
        params = model.init(jax.random.PRNGKey(1), x, masks)
        out = model.apply(params, x, masks)
        assert out.shape == (1, 2)
        assert np.isfinite(np.asarray(out)).all()

    def test_inactive_node_gradients_are_zero(self):
        """Masking correctness: a dropped node must not touch the loss.

        Genome (1, 0, 0) has the chain 0→1 and node 2 isolated — every
        gradient of stage0_node2's conv must be exactly zero, while active
        nodes' gradients are not.
        """
        model = MaskedGeneticCnn(
            nodes=(3,), filters=(4,), dense_units=8, n_classes=2,
            compute_dtype=jnp.float32,
        )
        masks = _masks_for({"S_1": (1, 0, 0)}, (3,))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8, 8, 1)), jnp.float32)
        variables = model.init(jax.random.PRNGKey(2), x, masks)

        def loss(params):
            return model.apply({"params": params}, x, masks).sum()

        grads = jax.grad(loss)(variables["params"])
        dead = grads["stage0_node2"]["kernel"]
        live = grads["stage0_node0"]["kernel"]
        assert np.all(np.asarray(dead) == 0.0)
        assert np.any(np.asarray(live) != 0.0)

    def test_isomorphic_genomes_same_program_different_masks(self):
        """1→2 chain vs 2→3 chain: same compiled fn, numerically same loss
        landscape up to parameter relabeling — here we just assert both run
        through one shared program (no retrace) and give finite outputs."""
        model = MaskedGeneticCnn(
            nodes=(3,), filters=(4,), dense_units=8, n_classes=2,
            compute_dtype=jnp.float32,
        )
        x = jnp.ones((1, 8, 8, 1))
        traces = []

        @jax.jit
        def fwd(params, masks):
            traces.append(1)
            return model.apply(params, x, masks)

        m1 = _masks_for({"S_1": (1, 0, 0)}, (3,))
        m2 = _masks_for({"S_1": (0, 0, 1)}, (3,))
        params = model.init(jax.random.PRNGKey(0), x, m1)
        out1 = fwd(params, m1)
        out2 = fwd(params, m2)
        assert len(traces) == 1  # masks are data: one trace serves all genomes
        assert np.isfinite(np.asarray(out1)).all() and np.isfinite(np.asarray(out2)).all()


class TestGeneticCnnModelCV:
    def test_learns_separable_data(self, separable_data):
        x, y = separable_data
        m = GeneticCnnModel(x, y, {"S_1": (1, 0, 1)}, **FAST)
        fit = m.cross_validate()
        assert 0.5 < fit <= 1.0

    def test_population_path_matches_shapes_and_learns(self, separable_data):
        x, y = separable_data
        genomes = [
            {"S_1": (0, 0, 0)},
            {"S_1": (1, 0, 1)},
            {"S_1": (1, 1, 1)},
        ]
        accs = GeneticCnnModel.cross_validate_population(x, y, genomes, **FAST)
        assert accs.shape == (3,)
        assert (accs > 0.4).all()

    def test_flat_input_reshape(self, separable_data):
        x, y = separable_data
        flat = x.reshape(x.shape[0], -1)
        m = GeneticCnnModel(
            flat, y, {"S_1": (1, 0, 1)}, input_shape=(8, 8, 1), **FAST
        )
        assert 0.5 < m.cross_validate() <= 1.0

    def test_compile_cache_no_retrace_across_calls(self, separable_data):
        from gentun_tpu.models.cnn import _fold_segment_fns

        x, y = separable_data
        GeneticCnnModel.cross_validate_population(x, y, [{"S_1": (0, 1, 0)}], **FAST)
        before = _fold_segment_fns.cache_info().hits
        GeneticCnnModel.cross_validate_population(x, y, [{"S_1": (1, 1, 0)}], **FAST)
        after = _fold_segment_fns.cache_info()
        # Identical static config: the segmented-factory must hit its cache
        # (same jitted program family for every genome — SURVEY.md §7 #1).
        assert after.hits > before

    def test_config_validation(self, separable_data):
        x, y = separable_data
        with pytest.raises(TypeError):
            GeneticCnnModel(x, y, {"S_1": (0, 0, 0)}, bogus_knob=3, **FAST).cross_validate()
        with pytest.raises(ValueError):
            GeneticCnnModel(
                x, y, {"S_1": (0, 0, 0)},
                nodes=(3,), kernels_per_layer=(8, 8), kfold=2,
                epochs=(1,), learning_rate=(0.1,), compute_dtype="float32",
            ).cross_validate()
        with pytest.raises(ValueError):  # epochs/lr not parallel
            GeneticCnnModel(
                x, y, {"S_1": (0, 0, 0)},
                nodes=(3,), kernels_per_layer=(8,), kfold=2,
                epochs=(1, 2), learning_rate=(0.1,), compute_dtype="float32",
            ).cross_validate()

    def test_staged_lr_schedule_runs(self, separable_data):
        x, y = separable_data
        m = GeneticCnnModel(
            x, y, {"S_1": (1, 1, 1)},
            nodes=(3,), kernels_per_layer=(8,), kfold=2,
            epochs=(1, 1), learning_rate=(0.05, 0.005),
            batch_size=32, dense_units=32, compute_dtype="float32", seed=1,
        )
        assert 0.0 <= m.cross_validate() <= 1.0


class TestFitnessReps:
    """fitness_reps=R (VERDICT r4 weak #1): per-evaluation fitness averaged
    over R independent trainings, tiled through the population vmap axis."""

    def test_reps_shape_and_agreement_with_per_seed_calls(self, separable_data):
        x, y = separable_data
        genomes = [{"S_1": (1, 0, 1)}, {"S_1": (0, 1, 1)}]
        accs = GeneticCnnModel.cross_validate_population(
            x, y, genomes, fitness_reps=2, **FAST
        )
        assert accs.shape == (2,)
        assert np.isfinite(accs).all() and (accs > 0.3).all()
        # Each rep is one full run at a derived seed: the average must
        # reproduce the mean of the explicit per-seed calls exactly.
        base = FAST["seed"]
        per_seed = [
            GeneticCnnModel.cross_validate_population(
                x, y, genomes, **{**FAST, "seed": base + 7919 * r}
            )
            for r in range(2)
        ]
        np.testing.assert_allclose(accs, np.mean(per_seed, axis=0), rtol=1e-6)

    def test_reps_are_independent_trainings(self, separable_data):
        """The derived-seed reps must not be bit-identical replays (they
        vary init, dropout, shuffle and folds), or averaging would remove
        nothing — this is the failure mode that sank the earlier pop-axis
        tiling design under the learned OOM chunk cap."""
        x, y = separable_data
        base = FAST["seed"]
        r0, r1 = (
            GeneticCnnModel.cross_validate_population(
                x, y, [{"S_1": (1, 0, 1)}], **{**FAST, "seed": base + 7919 * r}
            )[0]
            for r in range(2)
        )
        assert r0 != r1, (r0, r1)

    def test_reps_validation_and_instance_path(self, separable_data):
        x, y = separable_data
        with pytest.raises(ValueError):
            GeneticCnnModel.cross_validate_population(
                x, y, [{"S_1": (1, 0, 1)}], fitness_reps=0, **FAST
            )
        m = GeneticCnnModel(x, y, {"S_1": (1, 0, 1)}, fitness_reps=2, **FAST)
        assert 0.4 < m.cross_validate() <= 1.0

    def test_train_and_score_reps(self, separable_data):
        x, y = separable_data
        accs = GeneticCnnModel.train_and_score(
            x[:128], y[:128], x[128:], y[128:], [{"S_1": (1, 0, 1)}],
            fitness_reps=2, **FAST
        )
        assert accs.shape == (1,)
        assert 0.0 <= accs[0] <= 1.0


class TestStageExitConv:
    """Optional Xie & Yuille output-node conv (ADVICE r1, cnn.py stage exit)."""

    def test_exit_conv_params_exist_and_forward_works(self):
        model = MaskedGeneticCnn(
            nodes=(3,), filters=(4,), dense_units=8, n_classes=2,
            compute_dtype=jnp.float32, stage_exit_conv=True,
        )
        masks = _masks_for({"S_1": (1, 0, 1)}, (3,))
        x = jnp.ones((2, 8, 8, 1))
        params = model.init(jax.random.PRNGKey(0), x, masks)
        assert "stage0_exit" in params["params"]
        out = model.apply(params, x, masks)
        assert out.shape == (2, 2)
        assert np.isfinite(np.asarray(out)).all()

    def test_population_path_trains_with_exit_conv(self, separable_data):
        x, y = separable_data
        genomes = [{"S_1": (1, 0, 1)}, {"S_1": (0, 0, 0)}]
        accs = GeneticCnnModel.cross_validate_population(
            x, y, genomes, **{**FAST, "stage_exit_conv": True}
        )
        assert accs.shape == (2,)
        assert np.isfinite(accs).all()
        assert (accs > 0.25).all()  # beats 4-class chance


class TestTrainAndScore:
    def test_holdout_scores_match_separability(self, separable_data):
        x, y = separable_data
        x_tr, y_tr, x_te, y_te = x[:160], y[:160], x[160:], y[160:]
        genomes = [{"S_1": (1, 0, 1)}, {"S_1": (1, 1, 1)}]
        accs = GeneticCnnModel.train_and_score(
            x_tr, y_tr, x_te, y_te, genomes, **FAST
        )
        assert accs.shape == (2,)
        assert np.isfinite(accs).all()
        assert (accs > 0.25).all()  # beats 4-class chance on held-out data

    def test_holdout_single_genome_and_uneven_test(self, separable_data):
        x, y = separable_data
        # test block not divisible by batch_size: exercises padding weights
        accs = GeneticCnnModel.train_and_score(
            x[:150], y[:150], x[150:183], y[150:183], [{"S_1": (1, 0, 1)}], **FAST
        )
        assert accs.shape == (1,)
        assert 0.0 <= float(accs[0]) <= 1.0


class TestSegmentedExecution:
    """Default executor: host loop of bounded device calls (watchdog-safe)."""

    def test_segment_size_does_not_move_the_answer(self, separable_data):
        """Same schedule, same seeds: one call per fold and two-step
        segments must produce identical accuracies."""
        x, y = separable_data
        genomes = [{"S_1": (1, 0, 1)}, {"S_1": (1, 1, 1)}]
        seg_big = GeneticCnnModel.cross_validate_population(
            x, y, genomes, **{**FAST, "segment_steps": None}
        )
        seg_tiny = GeneticCnnModel.cross_validate_population(
            x, y, genomes, **{**FAST, "segment_steps": 2}
        )
        np.testing.assert_allclose(seg_big, seg_tiny, atol=1e-5)

    def test_segment_bounds(self):
        from gentun_tpu.models.cnn import _segment_bounds

        assert _segment_bounds(10, None) == [(0, 10)]
        assert _segment_bounds(10, 96) == [(0, 10)]
        assert _segment_bounds(10, 4) == [(0, 4), (4, 8), (8, 10)]
        assert _segment_bounds(8, 4) == [(0, 4), (4, 8)]


class TestPopBucketing:
    def test_bucket_function(self):
        from gentun_tpu.models.cnn import _pop_bucket

        # floor is 2: the singleton program is numerically distinct (purity)
        assert [_pop_bucket(n) for n in (1, 2, 3, 5, 8, 9, 15)] == [2, 2, 4, 8, 8, 16, 16]
        assert _pop_bucket(16) == 16 and _pop_bucket(20) == 20  # large = exact

    def test_small_batches_share_compiled_shape(self, separable_data):
        """Sizes 3 and 4 pad to the same bucket (4): the segmented factory's
        jitted fns see one shape, so the second call cannot retrace."""
        x, y = separable_data
        g = lambda bits: {"S_1": bits}
        a3 = GeneticCnnModel.cross_validate_population(
            x, y, [g((1, 0, 1)), g((0, 1, 0)), g((1, 1, 0))], **FAST
        )
        a4 = GeneticCnnModel.cross_validate_population(
            x, y, [g((1, 0, 1)), g((0, 1, 0)), g((1, 1, 0)), g((1, 1, 1))], **FAST
        )
        assert a3.shape == (3,) and a4.shape == (4,)
        # padding is invisible: shared genomes score identically across calls
        np.testing.assert_allclose(a3, a4[:3], atol=1e-5)

    def test_padding_disabled_keeps_exact_size(self, separable_data):
        x, y = separable_data
        accs = GeneticCnnModel.cross_validate_population(
            x, y, [{"S_1": (1, 0, 1)}] * 3, **{**FAST, "pop_padding": False}
        )
        assert accs.shape == (3,)


class TestDeviceDatasetCache:
    def test_cache_hits_across_calls_even_with_conversion(self, separable_data):
        """The cache keys on the CALLER's arrays, so flat inputs (reshaped
        fresh every call by _prepare_data) still hit."""
        from gentun_tpu.models import cnn as cnn_mod

        x, y = separable_data
        flat = np.ascontiguousarray(x.reshape(x.shape[0], -1))  # stable caller object
        cnn_mod._DATASET_CACHE.clear()
        cfg = {**FAST, "input_shape": (8, 8, 1)}
        GeneticCnnModel.cross_validate_population(flat, y, [{"S_1": (1, 0, 1)}], **cfg)
        assert len(cnn_mod._DATASET_CACHE) == 1
        (xref, yref, xd, yd) = next(iter(cnn_mod._DATASET_CACHE.values()))
        GeneticCnnModel.cross_validate_population(flat, y, [{"S_1": (0, 1, 0)}], **cfg)
        assert len(cnn_mod._DATASET_CACHE) == 1
        (xref2, yref2, xd2, yd2) = next(iter(cnn_mod._DATASET_CACHE.values()))
        assert xd2 is xd  # same device copy reused, no re-upload
        assert xref() is flat

    def test_dead_entries_evicted_on_lookup(self):
        from gentun_tpu.models import cnn as cnn_mod

        cnn_mod._DATASET_CACHE.clear()
        rng = np.random.default_rng(0)
        xa = rng.normal(size=(64, 8, 8, 1)).astype(np.float32)
        ya = rng.integers(0, 2, size=64).astype(np.int32)
        GeneticCnnModel.cross_validate_population(xa, ya, [{"S_1": (1, 0, 1)}], **FAST)
        assert len(cnn_mod._DATASET_CACHE) == 1
        del xa  # host array dies → entry must be evicted on next lookup
        xb = rng.normal(size=(64, 8, 8, 1)).astype(np.float32)
        yb = rng.integers(0, 2, size=64).astype(np.int32)
        GeneticCnnModel.cross_validate_population(xb, yb, [{"S_1": (1, 0, 1)}], **FAST)
        assert len(cnn_mod._DATASET_CACHE) == 1  # dead entry gone, live one present
        (xref, *_rest) = next(iter(cnn_mod._DATASET_CACHE.values()))
        assert xref() is xb


def test_eval_batch_size_properties():
    from gentun_tpu.models.cnn import _eval_batch_size

    for bs in (32, 128, 256):
        for n_val in (0, 1, bs - 1, bs, bs + 1, 4 * bs, 4 * bs + 1, 513, 5000):
            eval_bs, nvp = _eval_batch_size(bs, n_val)
            assert nvp >= n_val
            if n_val == 0:
                assert nvp == 0
                continue
            assert nvp % eval_bs == 0  # eval scan covers the block exactly
            assert eval_bs <= 4 * bs  # the documented eval-width bound
            # padding never exceeds one train batch + segment rounding
            assert nvp - n_val < bs + int(np.ceil(nvp / eval_bs))
    # the reviewer's unlucky case: fold 513 @ batch 128 wastes ≤ one batch
    eval_bs, nvp = _eval_batch_size(128, 513)
    assert nvp == 640 and eval_bs == 320


class TestOomChunking:
    """Deep configs (BASELINE #5) OOM a single chip when the whole
    population vmaps through one program; the evaluator must self-heal by
    chunking and remember the cap for the config."""

    def _fake_oom_run(self, fail_above):
        calls = []

        def run(genomes):
            calls.append(len(genomes))
            if len(genomes) > fail_above:
                raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating ...")
            return np.asarray([float(sum(g["S_1"])) for g in genomes])

        return run, calls

    def test_splits_on_oom_and_remembers_cap(self):
        from gentun_tpu.models import cnn as cnn_mod

        key = ("test-cfg-a",)
        cnn_mod._POP_PROGRAM_CAP.pop(key, None)
        run, calls = self._fake_oom_run(fail_above=16)
        genomes = [{"S_1": (1, 0, 1)} for _ in range(50)]
        out = cnn_mod._chunked_by_cap(run, genomes, key)
        assert out.shape == (50,) and (out == 2.0).all()
        # one failed 50-wide attempt, then power-of-two chunks (16s + tail)
        assert calls[0] == 50
        assert all(c <= 16 for c in calls[1:])
        assert cnn_mod._POP_PROGRAM_CAP[key] == 16
        # second call pre-chunks without re-discovering the OOM
        calls.clear()
        out2 = cnn_mod._chunked_by_cap(run, genomes, key)
        assert out2.shape == (50,) and 50 not in calls
        cnn_mod._POP_PROGRAM_CAP.pop(key, None)

    def test_non_oom_errors_propagate(self):
        from gentun_tpu.models import cnn as cnn_mod

        def run(genomes):
            raise ValueError("bad genome")

        with pytest.raises(ValueError, match="bad genome"):
            cnn_mod._chunked_by_cap(run, [{"S_1": (1,)}] * 4, ("test-cfg-b",))
        assert ("test-cfg-b",) not in cnn_mod._POP_PROGRAM_CAP

    def test_single_genome_oom_reraises(self):
        from gentun_tpu.models import cnn as cnn_mod

        def run(genomes):
            raise RuntimeError("RESOURCE_EXHAUSTED")

        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            cnn_mod._chunked_by_cap(run, [{"S_1": (1,)}], ("test-cfg-c",))

    def test_single_genome_oom_falls_back_to_exact_runner(self):
        """The compile bucket floors at 2, so a singleton OOM must retry
        via the UNPADDED runner (a genuinely 1-wide program) — and the
        learned cap=1 must route straight there on later generations."""
        from gentun_tpu.models import cnn as cnn_mod

        calls = []

        def run(genomes):
            calls.append(("padded", len(genomes)))
            raise RuntimeError("RESOURCE_EXHAUSTED")

        def run_exact(genomes):
            calls.append(("exact", len(genomes)))
            return np.full(len(genomes), 0.5, dtype=np.float32)

        key = ("test-cfg-exact",)
        try:
            got = cnn_mod._chunked_by_cap(run, [{"S_1": (1,)}], key, run_exact)
            assert got.tolist() == [0.5]
            assert calls == [("padded", 1), ("exact", 1)]
            assert cnn_mod._POP_PROGRAM_CAP[key] == 1
            # cap remembered: the padded runner is never tried again
            cnn_mod._chunked_by_cap(run, [{"S_1": (1,)}, {"S_1": (0,)}], key, run_exact)
            assert calls[2:] == [("exact", 1), ("exact", 1)]
        finally:
            cnn_mod._POP_PROGRAM_CAP.pop(key, None)

    def test_chunked_matches_manual_chunks_real_model(self, separable_data):
        """A capped run equals evaluating the same chunks directly — AND
        equals the unchunked run: PRNG keys are content-derived
        (``genome_hashes``), so chunking cannot move any fitness
        (``TestBatchCompositionPurity``)."""
        from gentun_tpu.models import cnn as cnn_mod
        from gentun_tpu.models.cnn import GeneticCnnModel

        x, y = separable_data
        genomes = [{"S_1": (1, 0, 0)}, {"S_1": (0, 1, 1)}, {"S_1": (1, 1, 1)}]
        cfg = dict(nodes=(3,), kernels_per_layer=(8,), dense_units=32,
                   kfold=2, epochs=(1,), learning_rate=(0.05,),
                   batch_size=32, compute_dtype="float32", seed=0)
        unchunked = np.asarray(
            GeneticCnnModel.cross_validate_population(x, y, genomes, **cfg)
        )
        want = np.concatenate([
            np.asarray(GeneticCnnModel.cross_validate_population(x, y, genomes[:2], **cfg)),
            np.asarray(GeneticCnnModel.cross_validate_population(x, y, genomes[2:], **cfg)),
        ])
        np.testing.assert_array_equal(want, unchunked)
        key = cnn_mod._oom_cap_key(cnn_mod._normalize_config(x, y, dict(cfg)))
        cnn_mod._POP_PROGRAM_CAP[key] = 2  # force chunking: 2 + 1
        try:
            got = GeneticCnnModel.cross_validate_population(x, y, genomes, **cfg)
        finally:
            cnn_mod._POP_PROGRAM_CAP.pop(key, None)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


#: A device as ``xla_cache._device_facts`` describes one: the CPU these tests
#: run on reports no memory limit, so there nothing outlives the process.
V5E = {"device_kind": "TPU v5 lite", "bytes_limit": 16_909_336_576, "local_devices": 1,
       "jax": "0.9.0", "jaxlib": "0.9.0", "platform_version": "libtpu 0.0.34"}


class Events:
    def __init__(self):
        self.items = []

    def record(self, rec):
        self.items.append(rec)

    def named(self, name):
        return [r.get("data") or r.get("attrs") for r in self.items if r.get("name") == name]


class TestOomCapOutlivesTheProcess:
    """The healer's cap is kept beside the compiled programs
    (``<cache dir>/.oom_caps.json``) under configuration, device and
    compiler, and read before a process's first attempt."""

    KEY = ("deep-cfg", (5, 5, 5), 256)
    GENOMES = [{"S_1": (1, 0, 1)} for _ in range(50)]

    @pytest.fixture
    def store(self, tmp_path, monkeypatch):
        from gentun_tpu.models import cnn as cnn_mod
        from gentun_tpu.telemetry import spans
        from gentun_tpu.utils import xla_cache

        facts, the_cpu = dict(V5E), xla_cache._device_facts
        monkeypatch.setattr(xla_cache, "_device_facts", lambda: dict(facts))
        monkeypatch.setattr(xla_cache, "_oom_caps_read", {})
        monkeypatch.setattr(cnn_mod, "_POP_PROGRAM_CAP", {})
        events = Events()
        spans.set_run_sink(events)
        spans.enable()

        class Store:
            cfg = {"cache_dir": str(tmp_path), "mesh": None}
            path = tmp_path / ".oom_caps.json"

            def __init__(self):
                self.facts, self.the_cpu, self.events = facts, the_cpu, events

            def new_process(self):
                cnn_mod._POP_PROGRAM_CAP.clear()
                xla_cache._oom_caps_read.clear()
                events.items.clear()

            def run(self, fail_above, genomes=TestOomCapOutlivesTheProcess.GENOMES,
                    key=TestOomCapOutlivesTheProcess.KEY, cfg=None, run_exact=None):
                """One ``_chunked_by_cap`` call: the widths it ran, in order."""
                run, calls = TestOomChunking()._fake_oom_run(fail_above)
                out = cnn_mod._chunked_by_cap(run, genomes, key, run_exact, cfg or self.cfg)
                assert out.shape == (len(genomes),)
                return calls

            def kept(self):
                return sorted(json.loads(self.path.read_text())["caps"].values()) if self.path.exists() else None

        try:
            yield Store()
        finally:
            spans.disable()
            spans.set_run_sink(None)

    def test_a_new_process_starts_at_the_kept_cap(self, store):
        from gentun_tpu.telemetry.registry import get_registry

        assert store.run(16) == [50, 16, 16, 16, 2]
        assert store.kept() == [16] and store.events.named("oom_cap_restored") == []
        restored = get_registry().counter("oom_cap_restored_total")
        before = restored.value
        store.new_process()
        assert store.run(16) == [16, 16, 16, 2]  # no failing attempt
        assert store.events.named("oom_cap_restored") == [{"genomes": 50, "cap": 16}]
        assert store.events.named("oom_split") == [] and store.events.named("oom_attempt") == []
        assert store.run(16) == [16, 16, 16, 2]  # and said once a process
        assert len(store.events.named("oom_cap_restored")) == 1 and restored.value == before + 1
        # a resumed search's smaller first batch runs at the width the cache was filled under
        store.new_process()
        assert store.run(16, genomes=self.GENOMES[:20]) == [16, 4]

    @pytest.mark.parametrize("what", ["config", "bytes_limit", "local_devices", "device_kind", "jax",
                                      "jaxlib", "platform_version", "mesh"])
    def test_any_difference_in_the_key_is_a_miss(self, store, what):
        assert store.run(16)[0] == 50 and store.kept() == [16]
        store.new_process()
        key, cfg = self.KEY, dict(store.cfg)
        if what == "config":
            key = self.KEY[:-1] + (128,)
        elif what == "mesh":
            cfg["mesh"] = "auto"  # eight virtual devices: (8, 1), not (1, 1)
        else:
            store.facts[what] = 2 * store.facts[what]
        assert store.run(16, key=key, cfg=cfg)[0] == 50  # today's behaviour: the attempt
        assert store.events.named("oom_cap_restored") == []
        assert store.kept() == [16, 16]  # and both entries are kept

    @pytest.mark.parametrize("content", [
        "not json at all", "[1, 2]", '{"caps": [16]}', '{"caps": {"another key": 16}}', "KEY: 16.5", "KEY: true",
        'KEY: "16"', "KEY: 1", "KEY: 0"],
        ids=["torn", "a_list", "caps_a_list", "foreign", "a_float", "a_bool", "a_string", "one", "zero"])
    def test_a_file_that_cannot_be_used_is_ignored(self, store, content, caplog):
        from gentun_tpu.utils import xla_cache

        if content.startswith("KEY: "):
            key = xla_cache.oom_cap_key(self.KEY, (1, 1))
            content = json.dumps({"caps": {key: json.loads(content[5:])}})
        store.path.write_text(content)
        with caplog.at_level("WARNING", logger="gentun_tpu"):
            assert store.run(16) == [50, 16, 16, 16, 2]
        said = [r for r in caplog.records if "ignoring the learned out-of-memory caps" in r.getMessage()]
        assert len(said) == (1 if '"caps": {' not in content else 0)  # one line a process, not one a read
        assert store.events.named("oom_cap_restored") == []
        assert store.kept()[-1] == 16  # and the healed cap replaces it

    @pytest.mark.parametrize("cache_dir", [None, False, "off"])
    def test_with_the_cache_off_nothing_is_read_or_written(self, store, tmp_path, monkeypatch, cache_dir):
        from gentun_tpu.utils import xla_cache

        assert os.environ["GENTUN_TPU_CACHE_DIR"] == "off"  # tests/conftest.py: what None resolves by
        store.run(16)
        reads = []
        monkeypatch.setattr(xla_cache, "_load_oom_caps", reads.append)
        before = sorted(os.listdir(tmp_path))
        store.new_process()
        assert store.run(16, cfg={"cache_dir": cache_dir, "mesh": None}) == [50, 16, 16, 16, 2]
        assert reads == [] and sorted(os.listdir(tmp_path)) == before
        assert store.events.named("oom_cap_restored") == []

    def test_a_backend_with_no_memory_limit_keeps_nothing(self, store, monkeypatch):
        from gentun_tpu.utils import xla_cache

        monkeypatch.setattr(xla_cache, "_device_facts", store.the_cpu)
        assert xla_cache.oom_cap_key(self.KEY, (1, 1)) is None
        assert store.run(16)[0] == 50 and store.kept() is None

    def test_a_cap_of_one_is_not_written(self, store):
        exact = []

        def run_exact(genomes):
            exact.append(len(genomes))
            return np.zeros(len(genomes))

        # three genomes halve to one; each then runs through the unpadded program
        assert store.run(1, genomes=self.GENOMES[:3], run_exact=run_exact) == [3] and exact == [1, 1, 1]
        assert store.kept() is None
        store.new_process()
        assert store.run(0, genomes=self.GENOMES[:1], run_exact=run_exact) == [1]  # the singleton branch
        assert store.kept() is None and exact == [1, 1, 1, 1]

    def test_a_restored_cap_that_still_fails_heals_and_replaces_the_entry(self, store):
        store.run(16)
        store.new_process()
        assert store.run(8) == [16, 8, 8, 8, 8, 8, 8, 2]  # one failing attempt, at the restored width
        assert store.kept() == [8]
        assert store.events.named("oom_split") == [{"genomes": 16, "cap": 8}]
        store.new_process()
        assert store.run(8) == [8, 8, 8, 8, 8, 8, 2]
        # the file never raises a cap a process has learned
        store.path.write_text(store.path.read_text().replace(": 8", ": 32"))
        assert store.run(8) == [8, 8, 8, 8, 8, 8, 2]

    def test_two_writers_with_different_keys_both_survive(self, store):
        from gentun_tpu.utils import xla_cache

        store.run(16)
        # another worker, another device: it read the directory before the first wrote
        store.new_process()
        xla_cache._oom_caps_read[store.cfg["cache_dir"]] = {}
        store.facts["bytes_limit"] = 8 << 30
        store.run(4)
        assert store.kept() == [4, 16]
        assert [n for n in os.listdir(store.cfg["cache_dir"]) if n != ".oom_caps.json"] == []  # no temporary left

    def test_the_model_hands_its_configuration_to_the_healer(self, store, separable_data, monkeypatch):
        """Through ``cross_validate_population``: the entry is keyed by the
        normalised configuration and lives in the directory it names."""
        from gentun_tpu.models import cnn as cnn_mod
        from gentun_tpu.models.cnn import GeneticCnnModel

        def too_big_above_two(cls, x, y, genomes, **config):
            if len(genomes) > 2:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory (made up by the test)")
            return np.zeros(len(genomes), np.float32)

        monkeypatch.setattr(GeneticCnnModel, "_cross_validate_population_one", classmethod(too_big_above_two))
        x, y = separable_data
        cfg = dict(nodes=(3,), kernels_per_layer=(8,), kfold=2, batch_size=32, mesh=None, **{"cache_dir": store.cfg["cache_dir"]})
        GeneticCnnModel.cross_validate_population(x, y, self.GENOMES[:5], **cfg)
        assert store.kept() == [2] and store.events.named("oom_split") == [{"genomes": 5, "cap": 2}]
        store.new_process()
        GeneticCnnModel.cross_validate_population(x, y, self.GENOMES[:5], **cfg)
        assert store.events.named("oom_cap_restored") == [{"genomes": 5, "cap": 2}]
        assert store.events.named("oom_split") == []
        (key,) = cnn_mod._POP_PROGRAM_CAP
        assert key == cnn_mod._oom_cap_key(cnn_mod._normalize_config(x, y, dict(cfg)))


class TestBatchCompositionPurity:
    """Fitness is a pure function of (architecture, config, seed).

    ``genome_hashes`` folds each slot's PRNG keys from genome content, so
    WHERE an architecture trains — slot index, batch composition,
    compile-bucket shape, alone or among others — cannot change its
    fitness.  This is the property the speculative-fill trajectory-identity
    claim and the cross-run fitness store both rest on (round-5 tailgen
    study measured a diverged search before this fix).

    The cross-bucket assertions below are EXACT on purpose: the suite is
    pinned to CPU (conftest), where XLA's different-program-shape
    compilations round identically, so any inequality here is an RNG
    regression, never float noise.  On TPU the same comparison may flip a
    rare validation sample across program shapes (PERF.md "Tail
    generations") — these tests are not meant to run there."""

    def test_fitness_invariant_to_slot_batch_and_bucket(self, separable_data):
        x, y = separable_data
        g = lambda bits: {"S_1": bits}
        a, b, c = g((1, 0, 1)), g((0, 1, 0)), g((1, 1, 1))
        batch = GeneticCnnModel.cross_validate_population(x, y, [a, b, c], **FAST)  # bucket 4
        alone = GeneticCnnModel.cross_validate_population(x, y, [b], **FAST)        # bucket 2
        swapped = GeneticCnnModel.cross_validate_population(
            x, y, [c, b, a, b, a], **FAST                                          # bucket 8
        )
        # exact equality: the per-slot streams are content-derived and the
        # per-slot math is slot-local, so not even float rounding may move
        assert alone[0] == batch[1]
        assert (swapped[0], swapped[1], swapped[2]) == (batch[2], batch[1], batch[0])
        assert swapped[3] == batch[1] and swapped[4] == batch[0]  # in-batch twins too

    def test_cross_session_packed_window_matches_solo_runs(self, separable_data):
        """The correctness gate for cross-session window packing (ISSUE
        19): two tenants' genomes interleaved slot-by-slot in ONE packed
        device window score EXACTLY what each tenant's solo windows score.
        This is the same purity invariant as above — batch composition is
        not a fitness input — asserted in the shape the broker's packer
        actually produces: a DRR-interleaved window of jobs from different
        sessions sharing one compile envelope."""
        x, y = separable_data
        g = lambda bits: {"S_1": bits}
        sess_a = [g((1, 0, 1)), g((0, 1, 0))]
        sess_b = [g((1, 1, 0)), g((0, 0, 1))]
        # One packed window, tenants interleaved: [a0, b0, a1, b1].
        packed = GeneticCnnModel.cross_validate_population(
            x, y, [sess_a[0], sess_b[0], sess_a[1], sess_b[1]], **FAST)
        solo_a = GeneticCnnModel.cross_validate_population(x, y, sess_a, **FAST)
        solo_b = GeneticCnnModel.cross_validate_population(x, y, sess_b, **FAST)
        assert (packed[0], packed[2]) == (solo_a[0], solo_a[1])
        assert (packed[1], packed[3]) == (solo_b[0], solo_b[1])

    def test_hashes_are_content_not_position(self):
        from gentun_tpu.models.evaluation import genome_hashes

        g1 = {"S_1": (1, 0, 1), "S_2": (0, 1, 1, 0, 0, 1)}
        g2 = {"S_1": (0, 1, 1), "S_2": (0, 1, 1, 0, 0, 1)}
        h = genome_hashes([g1, g2, g1])
        assert h.shape == (3, 2) and h.dtype == np.uint32  # 64 bits as two words
        assert tuple(h[0]) == tuple(h[2]) != tuple(h[1])
        # order of evaluation / position in the list is irrelevant
        assert tuple(genome_hashes([g2, g1])[1]) == tuple(h[0])

    def test_key_stream_domains_are_separated(self):
        """Init, CV-train, and holdout streams must never collide for one
        (seed, genome) — without the domain folds, train_and_score under
        the search's own seed would replicate CV fold-0 bit-for-bit and
        correlate the holdout estimate with the CV estimate it checks.
        Driven through the production constants and the production carry
        builder, not re-derived folds."""
        from gentun_tpu.models import cnn as cnn_mod
        from gentun_tpu.models import evaluation
        from gentun_tpu.models.cnn import MaskedGeneticCnn

        assert evaluation._INIT_DOMAIN and cnn_mod._HOLDOUT_DOMAIN and (
            evaluation._INIT_DOMAIN != cnn_mod._HOLDOUT_DOMAIN
        )
        h = evaluation.genome_hashes([{"S_1": (1, 0, 1)}])
        init_base, train_base = evaluation.base_keys(0)
        ho_init_base, ho_train_base = evaluation.base_keys(0, cnn_mod._HOLDOUT_DOMAIN)
        streams = [np.asarray(evaluation.fold_content_keys(base, 0, h))  # fold 0 of each stream
                   for base in (train_base, init_base, ho_train_base, ho_init_base)]
        for i, a in enumerate(streams):
            for b in streams[i + 1:]:
                assert not (a == b).all()

        # and the carry builder honors domain=: CV carries vs holdout
        # carries differ for the same (seed, genome), params and train keys
        model = MaskedGeneticCnn(nodes=(3,), filters=(4,), dense_units=8,
                                 n_classes=2, compute_dtype=jnp.float32)
        masks = jax.device_put(stack_genome_masks([{"S_1": (1, 0, 1)}], (3,)))
        cfg = {"seed": 0, "input_shape": (8, 8, 1)}
        _, ((cv_params, cv_rng),) = cnn_mod._fold_carries(cfg, model, masks, h, 1, None)
        _, ((ho_params, ho_rng),) = cnn_mod._fold_carries(
            cfg, model, masks, h, 1, None, domain=cnn_mod._HOLDOUT_DOMAIN)
        assert np.array_equal(cv_rng, streams[0]) and np.array_equal(ho_rng, streams[2])
        leaves_cv = jax.tree.leaves(cv_params)
        leaves_ho = jax.tree.leaves(ho_params)
        assert any(not np.array_equal(a, b) for a, b in zip(leaves_cv, leaves_ho))
