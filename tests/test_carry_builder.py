"""PR 26: one compiled program builds every fold's starting carry.

- ``_carry_fn``'s per-fold params and train keys are, bit for bit, what the
  stacked derivation gives (the eager code the builder replaced, kept here as
  the oracle: :func:`stacked_params`, :func:`stacked_keys`): CV and holdout
  domains, several widths, off and on a mesh;
- the public entry points return the fitness the parent commit (a9ecb14)
  returned for a seeded population: CV, holdout, a mesh and warm starts.
(The guard against the eager head coming back counts programs between spans:
``tests/test_tracing_scopes.py``.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gentun_tpu.models import cnn, evaluation
from gentun_tpu.models.cnn import GeneticCnnModel
from gentun_tpu.ops.dag import stack_genome_masks

NODES, FILTERS, SHAPE = (3, 2), (4, 6), (8, 8, 3)
GENOMES = [{"S_1": (1, 0, 1), "S_2": (1,)}, {"S_1": (0, 0, 0), "S_2": (0,)}, {"S_1": (1, 1, 1), "S_2": (0,)},
           {"S_1": (0, 1, 0), "S_2": (1,)}, {"S_1": (1, 1, 0), "S_2": (1,)}]
KW = dict(nodes=NODES, kernels_per_layer=FILTERS, kfold=2, epochs=(2,), learning_rate=(0.05,), batch_size=8,
          dense_units=12, cache_dir=False, seed=11, n_classes=5)


def images(n, seed):
    rng = np.random.default_rng(seed)
    protos = np.random.default_rng(5).normal(size=(5, *SHAPE)).astype(np.float32)
    y = rng.integers(0, 5, n).astype(np.int32)
    return protos[y] + 0.8 * rng.normal(size=(n, *SHAPE)).astype(np.float32), y


@pytest.fixture(scope="module")
def data():
    return images(96, 7)


# -- A. the builder against the stacked derivation ---------------------------------------


def stacked_keys(base_key, kfold, hashes):
    """(kfold, P, 2) PRNG keys, ``fold_content_keys`` stacked over the folds:
    eager, a few dispatches a fold."""
    h = jnp.asarray(hashes)
    return jnp.stack([evaluation.fold_content_keys(base_key, f, h) for f in range(kfold)])


def stacked_params(model, masks, kfold, seed, hashes, domain=0):
    """Per-(fold, individual) parameter init with a (kfold, P) prefix: one
    (fold x pop)-vmapped ``model.init`` over the stacked init keys."""
    keys = stacked_keys(evaluation.base_keys(seed, domain)[0], kfold, hashes)
    over_pop = jax.vmap(functools.partial(cnn._init_slot, model, SHAPE), in_axes=(0, 0))
    return jax.jit(jax.vmap(over_pop, in_axes=(0, None)))(keys, masks)


def leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb) and jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape and x.tobytes() == y.tobytes()


@pytest.mark.parametrize("width,mesh_shape", [(2, None), (5, None), (4, (4, 2)), (2, (2, 4))],
                         ids=["2_wide", "5_wide", "4_wide_mesh4x2", "2_wide_mesh2x4"])
@pytest.mark.parametrize("kfold,domain", [(1, cnn._HOLDOUT_DOMAIN), (2, 0)], ids=["holdout", "cv2"])
def test_carries_are_the_stacked_derivation_bit_for_bit(kfold, domain, width, mesh_shape):
    mesh = None
    if mesh_shape is not None:  # the callers pad a population to the mesh's pop axis
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(mesh_shape), ("pop", "data"))
    genomes = GENOMES[:width]
    seed = 11
    cfg = {"seed": seed, "input_shape": SHAPE}
    model = cnn.MaskedGeneticCnn(nodes=NODES, filters=FILTERS, dense_units=12, n_classes=5)
    stacked = jax.device_put(stack_genome_masks(genomes, NODES))
    hashes = evaluation.genome_hashes(genomes)

    masks, carries = cnn._fold_carries(cfg, model, stacked, hashes, kfold, mesh, domain=domain)

    params = stacked_params(model, stacked, kfold, seed, hashes, domain=domain)
    base = jax.random.PRNGKey(seed)
    keys = stacked_keys(jax.random.fold_in(base, domain) if domain else base, kfold, hashes)
    assert len(carries) == kfold
    for f, (p, rng) in enumerate(carries):
        leaves_equal(p, jax.tree.map(lambda a: a[f], params))
        leaves_equal(rng, keys[f])
    leaves_equal(masks, stacked)
    if mesh is not None:
        pop = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("pop"))
        assert all(leaf.sharding.is_equivalent_to(pop, leaf.ndim)
                   for leaf in jax.tree.leaves((masks, carries)))


# -- B. end to end: the parent's answers --------------------------------------------------

#: What the parent commit (a9ecb14, stacked init and eager per-fold slicing)
#: returned on this data, recorded before the code changed.  CPU, 8 virtual
#: devices: ``mesh="auto"`` is a (4, 2) mesh here, whose data axis reorders the
#: batch reductions, hence its own values.
PARENT = {
    "cv": (dict(mesh=None), 4, [0.2395833432674408, 0.3229166865348816, 0.3333333134651184, 0.3125]),
    "cv_kfold3": (dict(mesh=None, kfold=3), 3, [0.3125, 0.4583333432674408, 0.3125]),
    "cv_mesh": (dict(mesh="auto"), 4, [0.21875, 0.3541666865348816, 0.34375, 0.2916666865348816]),
    "cv_kfold3_mesh": (dict(mesh="auto", kfold=3), 3, [0.2916666567325592, 0.5416666865348816, 0.3125]),
}


@pytest.mark.parametrize("case", sorted(PARENT))
def test_cross_validate_population_returns_the_parents_fitness(data, case):
    over, n, expect = PARENT[case]
    got = GeneticCnnModel.cross_validate_population(*data, GENOMES[:n], **{**KW, **over})
    assert got.tolist() == expect


@pytest.mark.parametrize("mesh,expect", [(None, [0.34375, 0.21875, 0.28125, 0.1875]),
                                         ("auto", [0.28125, 0.21875, 0.59375, 0.3125])], ids=["one_device", "mesh"])
def test_train_and_score_returns_the_parents_fitness(data, mesh, expect):
    got = GeneticCnnModel.train_and_score(*data, *images(32, 8), GENOMES[:4], **{**KW, "mesh": mesh})
    assert got.tolist() == expect


def test_warm_start_returns_the_parents_fitness_cold_and_inherited(data, monkeypatch):
    monkeypatch.setattr(cnn, "_WARM_BANK", {})
    warm = {**KW, "mesh": None, "warm_start": True}
    cold = GeneticCnnModel.cross_validate_population(*data, GENOMES[:4], **warm)
    assert cold.tolist() == PARENT["cv"][2] and len(cnn._WARM_BANK) == 4
    inherited = GeneticCnnModel.cross_validate_population(*data, GENOMES[:4], **{**warm, "epochs": (3,)})
    assert inherited.tolist() == [0.2708333134651184, 0.5, 0.625, 0.5]
