"""What the tests of the routed family's architectures share (``test_routed_family*.py`` and the five
``test_<architecture>.py``; the sixth's is ``test_nemotron_h.py``): each architecture's table (its reference, its toy model, how the model's keys become
``Lfm2MoeModel``'s keyword arguments, its cases and tolerances), the loaders of ``benchmark/families/<name>/``, the
span sink, the kernels interpreted on the CPU, and the small functions every file wrote out for itself.

A seventh architecture adds an ``Arch`` here and cases to the family modules; its own file holds only what no other
architecture has.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import os
import re
import sys
from typing import Any, Callable, Dict, Optional

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import gentun_tpu
from gentun_tpu.models import lfm2_moe as M
from gentun_tpu.telemetry import spans
from gentun_tpu.telemetry.registry import get_registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
HIGHEST = jax.default_matmul_precision("highest")
STD = 0.15  # narrow layers: wider weights, or the operators vanish beside the residual
ROWS = np.array([[0, 1], [2, 3], [4, 5]], np.int32)  # the sequences of three train steps
SMALL_KERNEL_BLOCKS = dict(block_q=128, block_kv=128, block_kv_compute=128, block_q_dkv=128, block_kv_dkv=128,
                           block_kv_dkv_compute=128)


def _load(name: str, directory: str):
    """``<directory>/<name>.py`` as a module of its own (never in ``sys.modules``)."""
    spec = importlib.util.spec_from_file_location(
        f"{os.path.basename(directory)}_family_{os.path.basename(name)}", os.path.join(directory, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config_file(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


def traffic_mix(name: str = "lmpopeval_fresh") -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


def manifest() -> dict:
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        return json.load(fh)


#: what a family's files import by bare name, as ``run.py`` arranges it
_BARE_NAMES = ("lm_spans", "dsv2_spans", "mel_spans", "q3n_spans", "scope_rules", "scope_reduce", "stall_reduce", "spanlib",
               "trace_reduce", "flops", "family", "correct", "reference")


@contextlib.contextmanager
def as_run_py_loads(family: str):
    """``benchmark/families/<family>/`` and the harness's directory first on ``sys.path``, the bare names a family's
    files import put away and restored; yields a loader of the family's files (``"family"``) and of the readers
    (``"layer_metrics/<name>"``)."""
    directory = os.path.join(BENCH, "families", family)
    before = {n: sys.modules.pop(n, None) for n in _BARE_NAMES}
    sys.path[:0] = [directory, BENCH]

    def load(name: str):
        if name.startswith("layer_metrics/"):
            return _load(name.split("/", 1)[1], os.path.join(BENCH, "layer_metrics"))
        spec = importlib.util.spec_from_file_location(name, os.path.join(directory, name + ".py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module  # ``family.py`` is imported under its bare name by ``correct.py``
        spec.loader.exec_module(module)
        return module

    try:
        yield load
    finally:
        del sys.path[:2]
        for n in _BARE_NAMES:
            sys.modules.pop(n, None)
            if before[n] is not None:
                sys.modules[n] = before[n]


def family_module(family: str):
    """``families/<family>/family.py`` loaded as ``run.py`` loads it, and put away again."""
    with as_run_py_loads(family) as load:
        return load("family")


def published_cfg(family: str, config_name: str):
    """(the configuration file, the family's module, the ``Lfm2MoeConfig`` its cell runs)."""
    config, module = config_file(config_name), family_module(family)
    params = module.model_params(config, 5, False)
    params.pop("seed")
    x = np.zeros((config["n_sequences"], config["data"]["seq_len"]), np.int32)
    return config, module, M._normalize_config(x, params)[0]


def empty_run(config: dict, cell: str) -> dict:
    """A run in which nothing ran: what a program without the spans hands a reader."""
    return {"config": config, "cell": {"name": cell}, "chips": 1, "units": [], "records": [], "window": (0.0, 1.0),
            "elapsed": 1.0, "monitor": None, "trace": None, "memory_peak_bytes": 0, "peak": None}


# -- spans ------------------------------------------------------------------------------------------------------------


class _Sink:
    def __init__(self):
        self.records = []

    def record(self, rec):
        self.records.append(rec)


@contextlib.contextmanager
def traced():
    """Telemetry on, the registry empty, every record into the list this yields."""
    get_registry().reset()
    sink = _Sink()
    spans.set_run_sink(sink)
    spans.enable()
    try:
        yield sink.records
    finally:
        spans.disable()
        spans.set_run_sink(None)


def span_attrs(records, kind: Optional[str] = None, steps: Optional[int] = None) -> list:
    """The attributes of the spans of ``kind``, or of those that trained ``steps`` steps."""
    return [r.get("attrs") or {} for r in records if r["type"] == "span" and (kind is None or r["kind"] == kind)
            and (steps is None or (r.get("attrs") or {}).get("steps") == steps)]


def span(kind, t, attrs) -> dict:
    return {"type": "span", "kind": kind, "t_wall": t, "dur_s": 0.001, "attrs": attrs}


# -- the kernels on the CPU -------------------------------------------------------------------------------------------


@pytest.fixture()
def kernel_on_the_cpu(monkeypatch):
    """The fused attention core chosen whatever the backend, its kernels interpreted: the library's own factory is
    given ``interpret=True``, the program has no such knob."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    monkeypatch.setattr(splash, "make_splash_mqa_single_device",
                        functools.partial(splash.make_splash_mqa_single_device, interpret=True))
    monkeypatch.setattr(M, "_use_attention_kernel", lambda length: True)
    M._programs.cache_clear()
    M._kernel_visits.cache_clear()
    yield
    M._programs.cache_clear()
    M._kernel_visits.cache_clear()


def sparse_kernels_by_shape_alone(monkeypatch):
    """``_use_sparse_kernel`` without its backend half: where ``jax.default_backend()`` is the CPU and a program is
    lowered for, or interpreted as, a TPU's."""
    from gentun_tpu.models import sparse_kernel

    monkeypatch.setattr(M, "_use_sparse_kernel", lambda length, group, size, block: sparse_kernel.fits(
        length, group, size, min(block, length), M._SPARSE_GROUP * min(block, length), M._SPARSE_KERNEL_TILE))


@pytest.fixture()
def sparse_kernels_on_the_cpu(monkeypatch):
    """The masked core's fused kernels chosen by shape whatever the backend, and interpreted: the program has no such knob."""
    real = M._sparse_kernel_dims
    sparse_kernels_by_shape_alone(monkeypatch)
    monkeypatch.setattr(M, "_sparse_kernel_dims", lambda scale: real(scale)._replace(interpret=True))
    M._programs.cache_clear()
    yield
    M._programs.cache_clear()


@pytest.fixture()
def small_kernel_blocks(monkeypatch):
    """Blocks of 128: a few hundred positions are several blocks a side."""
    monkeypatch.setattr(M, "_ATTN_KERNEL_BLOCKS", dict(SMALL_KERNEL_BLOCKS))


def masks_handed_to_the_kernel(length, group, window):
    """The library's mask objects ``_splash_kernel`` would build the kernel from, evaluated on the host."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    real = splash.make_splash_mqa_single_device
    try:
        splash.make_splash_mqa_single_device = lambda mask, **kw: mask.masks
        return M._splash_kernel(length, group, window)
    finally:
        splash.make_splash_mqa_single_device = real


def rows_the_kernel_lets_through(length, group, window, head, rows):
    """What the windowed kernel's mask lets query head ``head`` of a key-value head see, as a 0/1 array
    (``rows``, ``length``) over absolute positions: the unbanded kernel's mask object as it is; the banded core's
    (one chunk's rectangle of ``group x chunk`` rows by ``chunk + window`` keys) laid back through the chunk layout
    of ``_banded_core`` -- row ``head x chunk + c`` of chunk ``i`` is query ``i x chunk + c``, its key ``j`` stands
    at ``i x chunk - window + j`` -- with the keys that ``_band_segments`` shuts out (and every key no chunk holds) 0."""
    chunk = M._kernel_chunk(length, window, group)
    masks = masks_handed_to_the_kernel(length, group, window)
    if not chunk:
        assert len(masks) == group
        return np.asarray(masks[head][rows, :]).astype(np.int32)
    (mask,), segments = masks, M._band_segments(length, window, chunk)
    assert mask.shape == (group * chunk, chunk + window) and segments.shape == (length // chunk, chunk + window)
    assert rows.start % chunk == 0 and rows.stop % chunk == 0
    seen = np.zeros((rows.stop - rows.start, length + window), np.int32)  # key j of chunk i at column i x chunk + j
    rectangle = np.asarray(mask[head * chunk:(head + 1) * chunk, :]).astype(np.int32)
    for i in range(rows.start // chunk, rows.stop // chunk):
        seen[i * chunk - rows.start:(i + 1) * chunk - rows.start, i * chunk:(i + 1) * chunk + window] = rectangle * segments[i]
    assert not seen[:, :window].any(), "a key before position 0 got through"
    return seen[:, window:]


# -- small functions --------------------------------------------------------------------------------------------------


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def value_and_gradients(operator, p, x):
    """(output, gradients of the weights, gradient of the input) of ``sum(operator(p, x) * probe)``, jitted."""
    probe = jnp.asarray(np.random.default_rng(1).normal(size=x.shape), jnp.float32)

    def value(p, x):
        out = operator(p, x)
        return jnp.sum(out.astype(jnp.float32) * probe), out

    (_, out), (dp, dx) = jax.jit(jax.value_and_grad(value, argnums=(0, 1), has_aux=True))(p, x)
    return out, dp, dx


def by_the_blockwise_core(operator, p, x):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(M, "_use_attention_kernel", lambda length: False)
        return value_and_gradients(operator, p, x)


def assert_within_bfloat16(got, want, names, floor: float = 0.5) -> None:
    """The output within two bfloat16 steps of its size, the gradients of the input and of every weight in
    ``names`` within 1% in norm."""
    (out, dp, dx), (ref, ref_dp, ref_dx) = got, want
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.abs(ref).max() > floor and np.abs(out - ref).max() <= 2.0 ** -7 * np.abs(ref).max()
    assert rel(dx, ref_dx) < 0.01
    for name in names:
        assert float(jnp.abs(ref_dp[name]).max()) > 0 and rel(dp[name], ref_dp[name]) < 0.01, name


def equations(jaxpr, scope=""):
    """(primitive, the named scopes it was traced under, its outputs' avals) of every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        here = "/".join(filter(None, [scope, str(eqn.source_info.name_stack)]))
        yield eqn.primitive.name, here, [v.aval for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub, here)


def scopes(fn, *args) -> set:
    """Every scope path an equation of ``fn``'s jaxpr carries, jax's transformation wrappers stripped."""
    found = set()

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            stack = "/".join(filter(None, (outer, re.sub(r"[A-Za-z_]+\(|\)", "", str(eqn.source_info.name_stack)))))
            if stack:
                found.add(stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, stack)  # a sub-jaxpr's stacks are relative to the equation that holds it

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return found


def program_steps(programs, weights, x, y, rows, steps, genes, bias=None):
    """``steps`` train steps of ``programs`` from ``weights`` (and ``bias``, where the rule reads one):
    (the state, the losses, the held experts' loads)."""
    state = programs.init(jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32))
    state = {**state, "params": jax.tree_util.tree_map(jnp.asarray, weights)}
    if bias is not None:
        state["bias"] = jnp.asarray(bias)
    losses, loads = [], []
    for s in range(steps):
        state, loss, held = programs.train_step(state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(rows),
                                                jnp.asarray(M.gene_vector(genes)), np.int32(s))
        losses.append(float(loss))
        loads.append(np.asarray(held))
    return state, losses, loads


def score_one(programs, x, y, genes, individual: int = 0, steps: int = 3):
    """One individual through ``_score_one`` as ``cross_validate_population`` calls it."""
    return M._score_one(programs, jax.random.PRNGKey(0), jnp.zeros(2, jnp.uint32), M.gene_vector(genes), jnp.asarray(x),
                        jnp.asarray(y), jnp.asarray([[0, 1], [2, 3], [0, 2]], np.int32), [jnp.asarray([4, 5])],
                        [np.int32(s) for s in range(steps)], individual)


def rope_table(rope: dict) -> tuple:
    """``rope_parameters`` by layer type as ``Lfm2MoeConfig`` holds it."""
    return tuple(sorted((k, tuple(sorted(b.items()))) for k, b in rope.items()))


# -- the architectures ------------------------------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arch:
    """One architecture of the routed family as its tests need it."""

    name: str                #: the id its cases carry
    family: str              #: ``benchmark/families/<family>/``
    model: Dict[str, Any]    #: the reference's toy model
    copied: tuple            #: the model's keys ``Lfm2MoeModel`` takes as they are
    extras: Callable[[Dict[str, Any]], Dict[str, Any]]  #: what the other keys become
    genes: Dict[str, float]
    genome: str              #: ``gentun_tpu.<genome>()``
    species: str             #: ``gentun_tpu.<species>``, where the architecture has one of its own
    positions: int           #: the length of the toy sequences
    weights: Dict[str, Any]  #: ``seeded_weights``' arguments after the model and the seed
    rule: str                #: "bias" or "aux_loss": who keeps the experts' load even
    layer_cases: Dict[str, Dict[str, Any]]
    step_model: Optional[Dict[str, Any]] = None  #: what the two train steps train, where not ``model``
    tol: Dict[str, float] = dataclasses.field(default_factory=dict)
    tied_embeddings: bool = False  #: under an untied head an embedding row no token reads gets no gradient
    gradient_bound_follows_its_size: bool = False  #: the gradients' bound is ``tol["gradient"] * max(|g|, 1)``

    @functools.cached_property
    def directory(self) -> str:
        return os.path.join(BENCH, "families", self.family)

    @functools.cached_property
    def R(self):
        return _load("reference", self.directory)

    @functools.cached_property
    def flops(self):
        return _load("flops", self.directory)

    @functools.cached_property
    def scope_rules(self):
        return _load("scope_rules", self.directory)

    @functools.cached_property
    def tokens(self):
        tok = np.random.default_rng(0).integers(0, 64, size=(10, self.positions + 1)).astype(np.int32)
        return tok[:, :-1], tok[:, 1:]

    def model_kwargs(self, m=None, **over) -> Dict[str, Any]:
        """``Lfm2MoeModel``'s keyword arguments that make it the reference's model ``m``."""
        m = self.model if m is None else m
        kw = {k: m[k] for k in self.copied}
        kw.update(batch_sequences=2, eval_sequences=2, attn_block=8, compute_dtype="float32")
        kw.update(self.extras(m))
        kw.update(over)
        return kw

    def config_of(self, m=None, tokens=None, **over) -> M.Lfm2MoeConfig:
        x = (self.tokens if tokens is None else tokens)[0]
        return M.Lfm2MoeModel.compiled_programs(x, **self.model_kwargs(m, **over)).config

    def seeded_weights(self, m, seed: int):
        return self.R.seeded_weights(m, seed, **self.weights)

    def routed_layers(self, m) -> int:
        kw = self.model_kwargs(m)
        if "routed" in kw["layer_types"]:  # a model whose blocks are a mixer alone or a routed feed-forward alone
            return kw["layer_types"].count("routed")
        return len(kw["layer_types"]) - kw["num_dense_layers"]

    def bias_of(self, m, seed=3, std=0.2):
        """A router bias large enough that ignoring it changes the choice (None where no rule reads one)."""
        if self.rule != "bias":
            return None
        experts = m["num_experts"] if "num_experts" in m else m["n_routed_experts"]
        return (std * np.random.default_rng(seed).standard_normal((self.routed_layers(m), experts))).astype(np.float32)

    def tolerance(self, name: str) -> float:
        return self.tol.get(name, TOLERANCES[name])


#: the comparisons' bounds unless an architecture's table says otherwise
TOLERANCES = dict(logits=2e-5, loss=1e-6, gradient=2e-6, step=3e-5, eval=2e-5, shares=2e-5)

YARN = dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707, mscale_all_dim=0.707,
             original_max_position_embeddings=4096, type="yarn")
_AUX_GENES = dict(log10_lr=-2.5, warmup_frac=0.5, weight_decay=0.1, beta2=0.95, aux_alpha=0.05)
_BIAS_GENES = dict(log10_lr=-2.5, warmup_frac=0.5, weight_decay=0.1, beta2=0.95, bias_step=0.01)

_LFM2 = dict(hidden_size=32, layer_types=["conv", "full_attention", "conv"], num_dense_layers=1, intermediate_size=48,
             moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2, held_experts=[2, 4],
             num_attention_heads=4, num_key_value_heads=2, vocab_size=64, conv_L_cache=3, norm_eps=1e-5,
             rope_parameters={"rope_theta": 1e6}, train_steps=3)


def lfm2_one_layer(kind: str, ffn: str):
    """A one-layer model of the given operator and feed-forward (a dense layer cannot stand alone: the program
    needs a routed one, so it leads one)."""
    types = [kind] if ffn == "moe" else [kind, "conv"]
    return {**_LFM2, "layer_types": types, "num_dense_layers": 0 if ffn == "moe" else 1, "held_experts": [1, 5]}


_DSV2 = dict(hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=48,
             moe_intermediate_size=24, n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=2,
             held_experts=[2, 4], num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
             v_head_dim=8, vocab_size=64, rms_norm_eps=1e-6, rope_theta=10000.0,
             rope_scaling={**YARN, "original_max_position_embeddings": 8}, train_steps=3)

MELLUM_ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                                  "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                                  "attention_factor": 1.2772588722239782},
               "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
MELLUM_PERIOD = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
_MELLUM = dict(hidden_size=40, head_dim=16, num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24,
               num_experts=8, num_experts_per_tok=3, held_experts=[2, 4], num_hidden_layers=4, layer_types=MELLUM_PERIOD,
               vocab_size=64, rms_norm_eps=1e-6, rope_parameters=MELLUM_ROPE, sliding_window=6, train_steps=3)

Q3N_PERIOD = ["linear_attention", "linear_attention", "linear_attention", "full_attention"]
_Q3N = dict(hidden_size=40, head_dim=16, num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24,
            shared_expert_intermediate_size=24, num_experts=8, num_experts_per_tok=3, held_experts=[2, 4],
            num_hidden_layers=4, layer_types=Q3N_PERIOD, vocab_size=64, rms_norm_eps=1e-6, rope_theta=1e7,
            partial_rotary_factor=0.25, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=12, linear_conv_kernel_dim=4, train_steps=3)

LAGUNA_ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                                  "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                                  "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
               "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}}
#: The cut's shape at toy widths: the dense layer under full attention, a sparse layer under each attention type;
#: 4 query heads in a full layer and 6 in a sliding one over 2 key-value heads (2 and 3 to each), a window of 6.
_LAGUNA = dict(hidden_size=40, head_dim=16, intermediate_size=56, moe_intermediate_size=24, shared_expert_intermediate_size=24,
               num_experts=8, num_experts_per_tok=3, held_experts=[2, 4], num_key_value_heads=2, num_hidden_layers=3,
               layer_types=["full_attention", "sliding_attention", "full_attention"],
               mlp_layer_types=["dense", "sparse", "sparse"], num_attention_heads_per_layer=[4, 6, 4], vocab_size=64,
               rms_norm_eps=1e-6, rope_parameters=LAGUNA_ROPE, sliding_window=6, moe_routed_scaling_factor=2.5,
               train_steps=3)


#: The cut's shape at toy widths, ``M E * E M``: 4 Mamba-2 heads of 8 in 2 groups with group 1 (heads 2-3) held, a
#: state of 6, chunks of 8; 4 query heads over 2 key-value heads, no rope; 16 experts of two matrices in a latent
#: state of 16 under a hidden size of 40, 2 held and 3 a token, the routed sum times 5; a shared expert of width 36.
NMH_BLOCKS = ["mamba2", "routed", "full_attention", "routed", "mamba2"]
_NMH = dict(hidden_size=40, head_dim=16, num_attention_heads=4, num_key_value_heads=2, mamba_num_heads=4, mamba_head_dim=8,
            n_groups=2, ssm_state_size=6, conv_kernel=4, chunk_size=8, n_routed_experts=16, num_experts_per_tok=3,
            held_experts=[2, 4], held_mamba_heads=[2, 4], moe_intermediate_size=24, moe_latent_size=16,
            moe_shared_expert_intermediate_size=36, routed_scaling_factor=5.0, num_hidden_layers=5, layer_types=NMH_BLOCKS,
            vocab_size=64, layer_norm_epsilon=1e-5, rope_theta=10000, time_step_min=0.001, time_step_max=0.1,
            time_step_floor=1e-4, train_steps=3)


#: The cut's shape at toy widths: two layers alike, 4 query heads over 2 key-value heads with the norm of q and k, rope
#: by sections (2, 3 and 3 of a head's 8 pairs), an indexer of 8 heads of 8 over one key head that keeps 6 keys a query
#: (24 positions in blocks of 8: the last two blocks' queries choose), 8 experts 3 a token.
_KEYE = dict(hidden_size=40, head_dim=16, num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24,
             num_experts=8, num_experts_per_tok=3, held_experts=[2, 4], num_hidden_layers=2, vocab_size=64,
             rms_norm_eps=1e-6, rope_theta=1e7, mrope_section=[2, 3, 3], indexer_num_heads=8, indexer_head_dim=8, topk=6,
             train_steps=3)


def nmh_blocks(*kinds, **over):
    """The toy model cut to the blocks ``kinds`` (a program needs a routed one: one follows a mixer that stands alone)."""
    kinds = list(kinds) if "routed" in kinds else list(kinds) + ["routed"]
    return {**_NMH, "num_hidden_layers": len(kinds), "layer_types": kinds, "held_experts": [1, 5], **over}


def laguna_one_layer(kind, heads, ffn="sparse", **over):
    return {**_LAGUNA, "num_hidden_layers": 1, "layer_types": [kind], "mlp_layer_types": [ffn],
            "num_attention_heads_per_layer": [heads], "held_experts": [1, 5], **over}


ARCHS = {arch.name: arch for arch in (
    Arch(name="lfm2_moe", family="lfm2_moe", model=_LFM2, copied=tuple(k for k in _LFM2 if k != "rope_parameters"),
         extras=lambda m: dict(rope_theta=m["rope_parameters"]["rope_theta"]),
         genes=_BIAS_GENES, genome="lfm2_moe_genome", species="Lfm2MoeIndividual", positions=16, weights={}, rule="bias",
         layer_cases={"conv_moe": lfm2_one_layer("conv", "moe"), "attention_moe": lfm2_one_layer("full_attention", "moe"),
                      "conv_dense": lfm2_one_layer("conv", "dense"),
                      "attention_dense": lfm2_one_layer("full_attention", "dense"),
                      "whole_cut": {**_LFM2, "held_experts": [1, 5]}},
         tol=dict(shares=1e-5), tied_embeddings=True),
    Arch(name="deepseek_v2", family="deepseek_v2", model=_DSV2,
         copied=("hidden_size", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts",
                 "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size",
                 "rope_theta", "rope_scaling", "train_steps"),
         extras=lambda m: dict(layer_types=("latent_attention",) * m["num_hidden_layers"],
                               num_dense_layers=m["first_k_dense_replace"], num_experts=m["n_routed_experts"],
                               held_experts=tuple(m["held_experts"]), norm_eps=m["rms_norm_eps"], scoring_func="softmax",
                               norm_topk_prob=False, balance_rule="aux_loss", tie_word_embeddings=False),
         genes=_AUX_GENES, genome="deepseek_v2_genome", species="DeepseekV2Individual", positions=16,
         weights=dict(std=STD), rule="aux_loss",
         layer_cases={"latent_routed_shared": {**_DSV2, "num_hidden_layers": 1, "first_k_dense_replace": 0,
                                               "held_experts": [1, 5]},
                      "latent_dense": {**_DSV2, "num_hidden_layers": 2, "held_experts": [1, 5]},  # a dense layer leads a routed one
                      "whole_cut": {**_DSV2, "held_experts": [1, 5]}}),
    Arch(name="mellum2", family="mellum", model=_MELLUM,
         copied=("hidden_size", "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                 "num_attention_heads", "num_key_value_heads", "vocab_size", "rope_parameters", "sliding_window",
                 "train_steps"),
         extras=lambda m: dict(layer_types=tuple(m["layer_types"]), num_dense_layers=0, held_experts=tuple(m["held_experts"]),
                               norm_eps=m["rms_norm_eps"], qk_norm=False, scoring_func="softmax", norm_topk_prob=True,
                               balance_rule="aux_loss", tie_word_embeddings=False),
         genes=_AUX_GENES, genome="deepseek_v2_genome", species="", positions=24,  # 24 positions: four windows of 6
         weights=dict(std=STD), rule="aux_loss",
         layer_cases={"a_windowed_layer": {**_MELLUM, "num_hidden_layers": 1, "layer_types": ["sliding_attention"],
                                           "held_experts": [1, 5]},
                      "a_full_layer": {**_MELLUM, "num_hidden_layers": 1, "layer_types": ["full_attention"],
                                       "held_experts": [1, 5]},
                      "one_period": {**_MELLUM, "held_experts": [1, 5]},
                      "two_periods": {**_MELLUM, "num_hidden_layers": 8, "layer_types": MELLUM_PERIOD * 2}}),
    Arch(name="qwen3_next", family="qwen3_next", model=_Q3N,
         copied=("hidden_size", "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                 "num_attention_heads", "num_key_value_heads", "vocab_size", "rope_theta", "partial_rotary_factor",
                 "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
                 "linear_conv_kernel_dim", "train_steps"),
         extras=lambda m: dict(layer_types=tuple(m["layer_types"]), num_dense_layers=0, held_experts=tuple(m["held_experts"]),
                               norm_eps=m["rms_norm_eps"], qk_norm=True, attn_output_gate=True, n_shared_experts=1,
                               shared_expert_gate=True, scoring_func="softmax", norm_topk_prob=True,
                               balance_rule="aux_loss", tie_word_embeddings=False, attn_block=7, delta_chunk=8),
         genes=_AUX_GENES, genome="deepseek_v2_genome", species="", positions=28,  # three and a half chunks of 8
         weights=dict(std=STD), rule="aux_loss",
         layer_cases={"a_delta_layer": {**_Q3N, "num_hidden_layers": 1, "layer_types": ["linear_attention"],
                                        "held_experts": [1, 5]},
                      "a_gated_attention_layer": {**_Q3N, "num_hidden_layers": 1, "layer_types": ["full_attention"],
                                                  "held_experts": [1, 5]},
                      "one_period": _Q3N},
         step_model={**_Q3N, "num_hidden_layers": 2, "layer_types": Q3N_PERIOD[2:]},  # one layer of each kind
         tol=dict(logits=3e-5, loss=2e-6, gradient=3e-5, step=1e-4, eval=5e-5, shares=3e-5),
         gradient_bound_follows_its_size=True),
    Arch(name="laguna", family="laguna", model=_LAGUNA,
         copied=("hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size", "num_experts",
                 "num_experts_per_tok", "num_key_value_heads", "vocab_size", "rope_parameters", "sliding_window",
                 "train_steps"),
         extras=lambda m: dict(layer_types=tuple(m["layer_types"]), num_attention_heads=4,
                               num_attention_heads_per_layer=tuple(m["num_attention_heads_per_layer"]),
                               num_dense_layers=m["mlp_layer_types"].count("dense"), held_experts=tuple(m["held_experts"]),
                               norm_eps=m["rms_norm_eps"], qk_norm=False, attn_head_gate=True, n_shared_experts=1,
                               scoring_func="sigmoid", norm_topk_prob=True, balance_rule="bias",
                               routed_scaling_factor=m["moe_routed_scaling_factor"], tie_word_embeddings=False),
         genes=_BIAS_GENES, genome="lfm2_moe_genome", species="", positions=24,  # 24 positions: four windows of 6
         weights=dict(std=STD, router_gain=3.0, gate_std=0.5), rule="bias",
         layer_cases={"a_full_layer_of_4_heads": laguna_one_layer("full_attention", 4),
                      "a_sliding_layer_of_6_heads": laguna_one_layer("sliding_attention", 6),
                      "a_sliding_layer_of_8_heads": laguna_one_layer("sliding_attention", 8),
                      "the_dense_layer_before_a_sparse_one": {
                          **_LAGUNA, "num_hidden_layers": 2, "layer_types": _LAGUNA["layer_types"][:2],
                          "mlp_layer_types": ["dense", "sparse"], "num_attention_heads_per_layer": [4, 6]},
                      "the_cut": _LAGUNA,
                      "two_periods": {**_LAGUNA, "num_hidden_layers": 5,
                                      "layer_types": ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
                                      "mlp_layer_types": ["dense"] + ["sparse"] * 4,
                                      "num_attention_heads_per_layer": [4, 6, 6, 6, 4]}},
         tol=dict(logits=3e-5, gradient=3e-6, eval=3e-5, shares=3e-5)),
    Arch(name="nemotron_h", family="nemotron_h", model=_NMH,
         copied=("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "mamba_num_heads", "mamba_head_dim",
                 "ssm_state_size", "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size", "routed_scaling_factor",
                 "vocab_size", "rope_theta", "train_steps"),
         extras=lambda m: dict(layer_types=tuple(m["layer_types"]), num_dense_layers=0, num_experts=m["n_routed_experts"],
                               held_experts=tuple(m["held_experts"]), mamba_n_groups=m["n_groups"],
                               mamba_conv_kernel=m["conv_kernel"], mamba_chunk=m["chunk_size"],
                               held_mamba_heads=tuple(m["held_mamba_heads"]), norm_eps=m["layer_norm_epsilon"], qk_norm=False,
                               positional_encoding="none", mlp_hidden_act="relu2", n_shared_experts=1,
                               shared_expert_intermediate_size=m["moe_shared_expert_intermediate_size"],
                               scoring_func="sigmoid", norm_topk_prob=True, balance_rule="bias", route_eps=1e-20,
                               tie_word_embeddings=False, attn_block=7),
         genes=_BIAS_GENES, genome="lfm2_moe_genome", species="", positions=28,  # three and a half chunks of 8
         weights=dict(std=STD, router_gain=3.0, conv_std=0.5), rule="bias",
         layer_cases={"a_mamba2_block": nmh_blocks("mamba2"), "an_attention_block": nmh_blocks("full_attention"),
                      "a_routed_block_alone": nmh_blocks("routed"),
                      "a_mamba2_block_of_both_groups": nmh_blocks("mamba2", held_mamba_heads=[0, 4]),
                      "the_cut": _NMH, "two_periods": nmh_blocks(*NMH_BLOCKS * 2)},
         tol=dict(logits=3e-5, gradient=3e-6, step=5e-5, eval=3e-5, shares=1e-4)),  # 64 shares added up in float32
    Arch(name="keye_vl2", family="keye_vl2", model=_KEYE,
         copied=("hidden_size", "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                 "num_attention_heads", "num_key_value_heads", "vocab_size", "rope_theta", "indexer_num_heads",
                 "indexer_head_dim", "train_steps"),
         extras=lambda m: dict(layer_types=("sparse_attention",) * m["num_hidden_layers"], num_dense_layers=0,
                               held_experts=tuple(m["held_experts"]), norm_eps=m["rms_norm_eps"], qk_norm=True,
                               sparse_topk=m["topk"], mrope_section=tuple(m["mrope_section"]), scoring_func="softmax",
                               norm_topk_prob=True, balance_rule="aux_loss", tie_word_embeddings=False),
         genes=_AUX_GENES, genome="deepseek_v2_genome", species="", positions=24,  # three blocks of 8, four times the 6 kept
         weights=dict(std=STD), rule="aux_loss",
         layer_cases={"a_sparse_layer": {**_KEYE, "num_hidden_layers": 1, "held_experts": [1, 5]},
                      "a_layer_that_keeps_every_key": {**_KEYE, "num_hidden_layers": 1, "topk": 24, "held_experts": [1, 5]},
                      "the_cut": {**_KEYE, "held_experts": [1, 5]}},
         tol=dict(logits=3e-5, gradient=3e-6, eval=3e-5, shares=3e-5)),
)}
def long_tokens():
    """6 sequences of 512 positions: the smallest at which a configuration's row buffer has the whole ladder."""
    tok = np.random.default_rng(9).integers(0, 64, size=(6, 513)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


#: A small shape at which the configuration itself gives the ladder's three heights.  LFM2: 2 x 512 tokens, top-2, 2
#: of 8 experts held -- 1,024 and 1,536 rows (two and three ``gmm`` tiles hold 1.25 and 2.75 times the mean share of
#: 512) under the worst case of 2,048.  DeepSeek-V2: 1,024 tokens, top-6, 8 of 32 held: a mean share of 1,536 rows.
#: Mellum2: the published routing at a small width (64 experts, 8 a token, 8 held); at 512 tokens the mean share is 512.
LFM2_LADDER = {**_LFM2, "layer_types": ["conv"], "num_dense_layers": 0}
DSV2_TOP_6 = {**_DSV2, "num_hidden_layers": 1, "first_k_dense_replace": 0, "n_routed_experts": 32,
              "num_experts_per_tok": 6, "held_experts": [4, 12]}
MELLUM_TOP_8 = {**_MELLUM, "num_hidden_layers": 1, "layer_types": ["full_attention"], "num_experts": 64,
                "num_experts_per_tok": 8, "held_experts": [8, 16]}
#: Nemotron-H: the published routing at a small width (22 a token, 8 held, of 128); at 512 tokens the mean share is
#: 704 rows and the worst case min(22, 8) x 512, which every token choosing all 8 held experts fills to its last row.
NMH_TOP_22 = nmh_blocks("routed", n_routed_experts=128, num_experts_per_tok=22, held_experts=[8, 16])
#: architecture: (model, the ladder's heights, the tokens a step routes, (dtype, tolerance) pairs)
LADDERS = {"lfm2_moe": (LFM2_LADDER, (1024, 1536, 2048), 1024, (("float32", 1e-6), ("bfloat16", 1e-2))),
           "deepseek_v2": (DSV2_TOP_6, (2048, 4608, 6144), 1024, (("float32", 1e-6), ("bfloat16", 1e-2))),
           "mellum2": (MELLUM_TOP_8, (1024, 1536, 4096), 512, (("float32", 1e-6),)),
           "nemotron_h": (NMH_TOP_22, (1024, 2048, 4096), 512, (("float32", 1e-6),))}


def genome_of(arch: Arch):
    return getattr(gentun_tpu, arch.genome)()


def cases(select: Callable[[Arch], Any], names=None) -> list:
    """``pytest.param(architecture, case)`` for every case ``select`` gives an architecture (all of them, or those
    in ``names``), the id naming both."""
    return [pytest.param(name, case, id=f"{name}-{case}") for name in (names or ARCHS) for case in select(ARCHS[name])]
