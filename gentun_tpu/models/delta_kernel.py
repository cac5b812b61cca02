"""The gated delta rule's chunked core as two Pallas TPU kernels, forward and backward.

The rule and its chunked (WY / UT) form are :func:`gentun_tpu.models.lfm2_moe._delta_core_xla`'s, which stays as XLA's
ops, as the path of every backend that is no TPU and as the oracle these kernels are tested against.  What differs is
where a chunk's intermediates live.  A grid step takes one (sequence, key head) through ``steps`` chunks; a chunk's
``k k'``, ``q k'``, the decays, the unit lower-triangular system, its inverse, ``[U | W]`` and what the chunk writes
stay in fast memory from the operands to the output, and the state (key size x value size a value head, float32) is
carried from chunk to chunk, in values inside a grid step and in scratch between grid steps.  The forward kernel
writes ``o`` and, when a backward pass will follow, the state that entered each chunk; the backward kernel walks the
chunks in reverse carrying the state's cotangent, rebuilds a chunk's intermediates from the operands and that state,
and writes the operands' cotangents.  Nothing else reaches HBM.

The value heads of a key head run as one system: their chunks are stacked along the rows (``rows = value heads a key
head x chunk``: 2 x 64 = 128, one tile of the matrix unit), ``k k'`` and ``q k'`` are formed once for all of them, and
a mask keeps a head's rows to its own columns.  Every product is float32 at HIGHEST, and every exponent is a
difference ``G_i - G_j`` with ``j <= i``, a ``G_i`` or ``G_last - G_i``: none is positive, as the XLA form promises.

The system is inverted by block substitution, doubling: with ``T_b`` the inverse of the diagonal blocks of ``b`` rows
and ``C_b`` the system's entries inside the blocks of ``2 b`` rows but outside those of ``b``, ``T_2b = T_b - T_b C_b
T_b`` (``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``), up to the chunk.  That keeps the conditioning of
substitution: the product form ``(I - N)(I + N^2)(I + N^4)...`` is exact on paper and loses every digit when ``beta``
is near 1 on repeated keys under a weak decay (the powers of ``N`` grow like binomials).  A dense level is two
products of rows x rows x rows that multiply mostly zeros (PR 43 paid ten a system, 21 M multiply-adds for what
substitution does in 87 k), so each level takes the cheapest form its shape allows (:func:`_inverse_forms`): the
blocks of ``CLOSED_ROWS`` rows are substitution written out on the vector unit, with no product at all
(:func:`_closed_inverse`); a level whose ``b`` is whole sublanes runs both products at the ``rows / 2`` rows of
``C_b`` that are not zero, which are the only rows ``T_b C_b T_b`` has; a level that aligns with nothing keeps the
dense form.  At the published shape (two value heads, chunks of 64: 128 rows) that is the closed form at 4 rows, the
level of 4 dense, the levels of 8, 16 and 32 at half the rows: five product-equivalents a system
(:func:`inverse_products`, the ``train`` spans' ``linear_core_inverse_products``).  The products of a system wait on
each other, and a product of 128 rows is far shorter than its latency, so what a chunk needs that no state enters
(the inverse above all) is computed for all the chunks of a grid step together, level by level
(:func:`_unit_lower_inverses`), before the state walks them: alone a layer's forward took 17.3 ms with one chunk's
chain at a time (PERF.md section 6, PR 43).
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EXACT = dict(precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
#: The chunks a grid step walks, at most, their state-free work advanced together (the module's docstring): alone on
#: the chip the forward kernel took 17.3 ms a layer at one, 11.6 at two, 11.3 at four; eight do not fit fast memory.
MAX_STEPS = 4
#: Of the chip's 16 MB of fast memory, what a grid step's pipelined blocks may take; the rest is a chunk's intermediates,
#: a dozen or two arrays of rows x rows and rows x a head's columns, which is why a key head's value heads x chunk is bounded.
BLOCK_BYTES, MAX_ROWS = 6 * 2 ** 20, 256
#: The diagonal blocks of a chunk's system that are inverted in closed form on the vector unit (a power of two; alone
#: on the chip blocks of 8 lost to the two products they save, PERF.md section 6, PR 44), and the rows a slice has to
#: be whole numbers of for a doubling level to run at its live rows alone (a float32 tile's).
CLOSED_ROWS, SUBLANES = 4, 8


class Dims(NamedTuple):
    """The static sizes of a call: positions a chunk, value heads a key head, key and value size a head, chunks a
    grid step, and whether Pallas interprets the kernels (the CPU's tests) instead of compiling them."""
    chunk: int
    heads: int
    dk: int
    dv: int
    steps: int
    interpret: bool


def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), **_EXACT)


def _mm_nt(a, b):
    """``a b'``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), **_EXACT)


def _mm_tn(a, b):
    """``a' b``."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), **_EXACT)


def _row_sums(a):
    return jnp.sum(a, axis=1, keepdims=True)


def _stacked(a, heads: int):
    """A key head's (chunk, size) operand once a value head, along the rows."""
    return a if heads == 1 else jnp.concatenate([a] * heads, axis=0)


def _by_head(ref, at: slice, d: Dims):
    """The positions ``at`` of a block (1, 1, heads, positions, value size), a value head after the other along the rows."""
    return _per_head(lambda h: ref[0, 0, h, at, :], d)


def _of_head(a, h: int, d: Dims):
    return a[h * d.chunk:(h + 1) * d.chunk]


def _per_head(fn, d: Dims):
    """``fn(h)`` of every value head, stacked along the rows."""
    parts = [fn(h) for h in range(d.heads)]
    return parts[0] if d.heads == 1 else jnp.concatenate(parts, axis=0)


class _Masks(NamedTuple):
    """The masks of a (rows, rows) array that every chunk of a call shares: made once a grid step, not once a chunk."""
    eye: jax.Array
    below: jax.Array  # strictly below the diagonal, within a head
    at_or_below: jax.Array
    last: jax.Array  # a row's own head's last column
    diagonals: tuple  # for s = 1, 2, ...: the s-th diagonal below the main one, within the diagonal blocks of CLOSED_ROWS rows
    levels: tuple  # for b = CLOSED_ROWS, 2 b, ...: (b, form, within the diagonal blocks of 2 b rows and outside those of b: of a halved level's live rows alone)


def _inverse_forms(chunk: int, heads: int):
    """How the inverse of a chunk's system is built, by what can be seen of the shape: ``(b, form)`` of each level in
    turn.  ``closed``: the diagonal blocks of ``b`` rows by substitution written out on the vector unit; then the
    doubling levels ``b -> 2 b``, ``halved`` (both products at the ``rows / 2`` rows that are not zero) where those
    rows are whole sublanes in every block of ``2 b``, ``dense`` (two products of rows x rows x rows) where not."""
    rows = heads * chunk
    forms, b = [(CLOSED_ROWS, "closed")], CLOSED_ROWS
    while b < rows:
        if b % chunk:  # blocks that are whole heads have nothing between them
            forms.append((b, "halved" if b % SUBLANES == 0 and rows % (2 * b) == 0 else "dense"))
        b *= 2
    return tuple(forms)


def inverse_products(chunk: int, heads: int) -> float:
    """The products of rows x rows x rows a chunk's inverse costs as :func:`_inverse_forms` builds it (the ``train``
    spans' ``linear_core_inverse_products``): two a dense level, two halves a halved one, none in closed form."""
    return sum({"closed": 0.0, "halved": 1.0, "dense": 2.0}[form] for _, form in _inverse_forms(chunk, heads))


def _masks(d: Dims) -> _Masks:
    rows = d.heads * d.chunk
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)

    def head(at):  # by selects: Mosaic has no comparison of two masks, which is what a sum of casts becomes
        return functools.reduce(lambda of, h: jnp.where(at >= h * d.chunk, h, of), range(1, d.heads), jnp.zeros_like(at))

    same = head(i) == head(j)
    closed = CLOSED_ROWS.bit_length() - 1
    levels = []
    for b, form in _inverse_forms(d.chunk, d.heads)[1:]:
        level = b.bit_length() - 1
        if form == "halved":  # of the live rows alone: row r of them is row b + r % b of block r // b
            r = jax.lax.broadcasted_iota(jnp.int32, (rows // 2, rows), 0)
            levels.append((b, form, (jax.lax.broadcasted_iota(jnp.int32, (rows // 2, rows), 1) >> level) == ((r >> level) << 1)))
        else:
            levels.append((b, form, ((i >> (level + 1)) == (j >> (level + 1))) & ((i >> level) != (j >> level))))
    return _Masks(eye=i == j, below=same & (j < i), at_or_below=same & (j <= i), last=j == (head(i) + 1) * d.chunk - 1,
                  diagonals=tuple(((i >> closed) == (j >> closed)) & (i - j == s) for s in range(1, CLOSED_ROWS)),
                  levels=tuple(levels))


def _closed_inverse(system, m: _Masks):
    """``(I + system)^-1`` within the diagonal blocks of ``CLOSED_ROWS`` rows, by substitution written out: ``T (I +
    system) = I`` column by column is ``T_s = -P_s - sum_t roll(T_(s - t), t columns) c_t`` for the ``s``-th diagonal
    of ``T`` below the main one, ``P_s`` that diagonal of the system and ``c_t`` its ``t``-th as a row (a product with
    a ``c_t`` lands on the ``s``-th diagonal of a block and nowhere else, so nothing needs a mask but ``P_s``).  Rolls
    along the lanes, sums along the sublanes and elementwise float32 arithmetic: no product of the matrix unit."""
    rows = system.shape[0]
    found, as_rows = [], []  # T_1, T_2, ... and c_1, c_2, ...
    for s, diagonal in enumerate(m.diagonals, start=1):
        below = jnp.where(diagonal, system, 0.0)
        found.append(-below - sum(pltpu.roll(found[s - t - 1], rows - t, 1) * as_rows[t - 1] for t in range(1, s)))
        as_rows.append(jnp.sum(below, axis=0, keepdims=True))
    return sum(found, jnp.where(m.eye, 1.0, 0.0))


def _halves(a, b: int):
    """(the upper, the lower) ``b`` rows of every block of ``2 b`` rows of ``a``, one block's after the other's:
    slices of whole sublanes."""
    blocks = range(0, a.shape[0], 2 * b)
    return jnp.concatenate([a[at:at + b] for at in blocks], axis=0), jnp.concatenate([a[at + b:at + 2 * b] for at in blocks], axis=0)


def _interleaved(upper, lower, b: int):
    """:func:`_halves` back in place."""
    return jnp.concatenate([part for at in range(0, upper.shape[0], b) for part in (upper[at:at + b], lower[at:at + b])], axis=0)


def _unit_lower_inverses(systems, m: _Masks):
    """``(I + system)^-1`` of each of ``systems``, strictly lower triangular (rows x rows, zero between heads), by
    block substitution, doubling (the module's docstring), each level in the form :func:`_inverse_forms` gave it.
    The systems advance level by level together: a level's two products wait on each other, those of different
    systems do not, so the matrix units always have one to run."""
    inverses = [_closed_inverse(system, m) for system in systems]
    for b, form, corner in m.levels:
        if form == "halved":  # C_b has entries in the lower b rows of a block of 2 b alone, and so have C_b T_b and T_b C_b T_b
            split = [_halves(inverse, b) for inverse in inverses]
            halves = [_mm(jnp.where(corner, _halves(system, b)[1], 0.0), inverse) for system, inverse in zip(systems, inverses)]
            moved = [_mm(lower, _interleaved(jnp.zeros_like(half), half, b)) for (_, lower), half in zip(split, halves)]
            inverses = [_interleaved(upper, lower - move, b) for (upper, lower), move in zip(split, moved)]
        else:
            halves = [_mm(jnp.where(corner, system, 0.0), inverse) for system, inverse in zip(systems, inverses)]
            inverses = [inverse - _mm(inverse, half) for inverse, half in zip(inverses, halves)]
    return inverses


class _Chunk(NamedTuple):
    """What a chunk's forward and backward share, none of which needs the state."""
    q: jax.Array  # (rows, dk): the key head's q, once a value head
    k: jax.Array
    v: jax.Array  # (rows, dv)
    beta: jax.Array  # (rows, 1)
    fallen: jax.Array  # exp(G), (rows, 1)
    to_end: jax.Array  # exp(G_last - G), (rows, 1)
    decay: jax.Array  # D, (rows, rows), zero above the diagonal and between heads
    kk: jax.Array
    qk: jax.Array
    left: list  # exp(G_last) a head, (1, 1)
    inverse: jax.Array = None  # T = (I + tril(beta k k' D, -1))^-1
    values: jax.Array = None  # U
    reads: jax.Array = None  # W


def _chunks(q_ref, k_ref, v_ref, gates_ref, d: Dims, m: _Masks):
    """The :class:`_Chunk` of each of a grid step's chunks.  Of the refs' blocks a chunk holds ``q``, ``k`` (chunk,
    dk), ``v`` (heads, chunk, dv) and ``gates`` (2, rows): the running sum of ``g`` from the chunk's start and
    ``beta``, a value head after the other along the lanes."""
    found = []
    for t in range(d.steps):
        at = slice(t * d.chunk, (t + 1) * d.chunk)
        q, k, gates = q_ref[0, 0, at, :], k_ref[0, 0, at, :], gates_ref[0, 0, t]
        as_column = lambda row: _row_sums(jnp.where(m.eye, row, 0.0))  # exact: one term a row
        fall_row, fall, beta = gates[0:1], as_column(gates[0:1]), as_column(gates[1:2])
        decay = jnp.where(m.at_or_below, jnp.exp(jnp.where(m.at_or_below, fall - fall_row, 0.0)), 0.0)
        last = _row_sums(jnp.where(m.last, fall_row, 0.0))  # G at the end of a row's own head
        against_keys = _mm_nt(jnp.concatenate([q, k], axis=0), _stacked(k, d.heads))  # q k' and k k', once for every value head
        qk, kk = _stacked(against_keys[:d.chunk], d.heads), _stacked(against_keys[d.chunk:], d.heads)
        found.append(_Chunk(q=_stacked(q, d.heads), k=_stacked(k, d.heads), v=_by_head(v_ref, at, d), beta=beta, fallen=jnp.exp(fall),
                            to_end=jnp.exp(last - fall), decay=decay, kk=kk, qk=qk,
                            left=[jnp.exp(last[h * d.chunk:h * d.chunk + 1]) for h in range(d.heads)]))
    inverses = _unit_lower_inverses([jnp.where(m.below, c.beta * c.kk * c.decay, 0.0) for c in found], m)
    return [c._replace(inverse=inverse, values=_mm(inverse, c.beta * c.v), reads=_mm(inverse, (c.beta * c.fallen) * c.k))
            for c, inverse in zip(found, inverses)]


def _written(c: _Chunk, states, d: Dims):
    """``U - W S``: what the chunk's positions write, given the state that entered it."""
    return _per_head(lambda h: _of_head(c.values, h, d) - _mm(_of_head(c.reads, h, d), states[h]), d)


def _chunk_forward(c: _Chunk, states, d: Dims):
    """(the outputs (rows, dv), the states that leave the chunk)."""
    written = _written(c, states, d)
    seen = c.fallen * c.q
    out = _mm(c.qk * c.decay, written) + _per_head(lambda h: _mm(_of_head(seen, h, d), states[h]), d)
    k_to_end = c.to_end * c.k
    left = [c.left[h] * states[h] + _mm_tn(_of_head(k_to_end, h, d), _of_head(written, h, d)) for h in range(d.heads)]
    return out, left


def _chunk_backward(c: _Chunk, m: _Masks, states, sent, d_left, d: Dims):
    """The transpose of :func:`_chunk_forward`: ``sent`` the outputs' cotangent (rows, dv), ``d_left`` that of the
    states that left.  Returns (dq, dk (chunk, dk), dv (rows, dv), the gates' cotangent (2, rows), that of the
    states that entered)."""
    written = _written(c, states, d)
    seen, k_to_end, reach = c.fallen * c.q, c.to_end * c.k, c.qk * c.decay
    d_written = _mm_tn(reach, sent) + _per_head(lambda h: _mm(_of_head(k_to_end, h, d), d_left[h]), d)
    d_seen = _per_head(lambda h: _mm_nt(_of_head(sent, h, d), states[h]), d)
    d_reach = jnp.where(m.at_or_below, _mm_nt(sent, written), 0.0)
    d_k_to_end = _per_head(lambda h: _mm_nt(_of_head(written, h, d), d_left[h]), d)
    d_reads = _per_head(lambda h: -_mm_nt(_of_head(d_written, h, d), states[h]), d)
    d_entered = [c.left[h] * d_left[h] + _mm_tn(_of_head(seen, h, d), _of_head(sent, h, d))
                 - _mm_tn(_of_head(c.reads, h, d), _of_head(d_written, h, d)) for h in range(d.heads)]
    # through the solve: X = T R gives dR = T' dX and d(system) = -dR X'
    d_wrote_v, d_wrote_k = _mm_tn(c.inverse, d_written), _mm_tn(c.inverse, d_reads)
    d_system = jnp.where(m.below, -(_mm_nt(d_wrote_v, c.values) + _mm_nt(d_wrote_k, c.reads)), 0.0)
    k_dot = _row_sums(d_wrote_k * c.k)
    d_system_decay = d_system * c.decay
    d_beta = _row_sums(d_wrote_v * c.v) + c.fallen * k_dot + _row_sums(d_system_decay * c.kk)
    d_fallen = c.beta * k_dot + _row_sums(d_seen * c.q)
    d_kk, d_qk = c.beta * d_system_decay, d_reach * c.decay
    d_k = (c.beta * c.fallen) * d_wrote_k + c.to_end * d_k_to_end + _mm(d_kk, c.k) + _mm_tn(d_kk, c.k) + _mm_tn(d_qk, c.q)
    d_q = c.fallen * d_seen + _mm(d_qk, c.k)
    d_decay = (d_system * (c.beta * c.kk) + d_reach * c.qk) * c.decay  # times D: the exponent's cotangent
    to_end = _row_sums(d_k_to_end * c.k) * c.to_end
    d_fall = _row_sums(d_decay) + d_fallen * c.fallen - to_end
    row = jax.lax.broadcasted_iota(jnp.int32, d_fall.shape, 0)
    for h in range(d.heads):  # G_last is the head's last G: what exp(G_last - G) and exp(G_last) sent lands there
        d_last = jnp.sum(_of_head(to_end, h, d), axis=0, keepdims=True) \
            + c.left[h] * jnp.sum(_row_sums(d_left[h] * states[h]), axis=0, keepdims=True)
        d_fall = d_fall + jnp.where(row == (h + 1) * d.chunk - 1, d_last, 0.0)
    as_row = lambda column: jnp.sum(jnp.where(m.eye, column, 0.0), axis=0, keepdims=True)
    d_gates = jnp.concatenate([as_row(d_fall) - jnp.sum(d_decay, axis=0, keepdims=True), as_row(d_beta)], axis=0)
    over_heads = lambda a: sum((_of_head(a, h, d) for h in range(1, d.heads)), _of_head(a, 0, d))
    return over_heads(d_q), over_heads(d_k), c.beta * d_wrote_v, d_gates, d_entered


def _forward_kernel(q_ref, k_ref, v_ref, gates_ref, out_ref, *rest, d: Dims, keep: bool):
    entered_ref, state_ref = rest if keep else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    states = [state_ref[h] for h in range(d.heads)]
    for t, c in enumerate(_chunks(q_ref, k_ref, v_ref, gates_ref, d, _masks(d))):
        if keep:
            for h in range(d.heads):
                entered_ref[0, 0, t, h] = states[h]
        out, states = _chunk_forward(c, states, d)
        for h in range(d.heads):
            out_ref[0, 0, h, t * d.chunk:(t + 1) * d.chunk, :] = _of_head(out, h, d)
    for h in range(d.heads):
        state_ref[h] = states[h]


def _backward_kernel(q_ref, k_ref, v_ref, gates_ref, entered_ref, sent_ref, dq_ref, dk_ref, dv_ref, dgates_ref,
                     d_state_ref, *, d: Dims):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    m = _masks(d)
    d_left = [d_state_ref[h] for h in range(d.heads)]
    for t, c in reversed(list(enumerate(_chunks(q_ref, k_ref, v_ref, gates_ref, d, m)))):
        at = slice(t * d.chunk, (t + 1) * d.chunk)
        states = [entered_ref[0, 0, t, h] for h in range(d.heads)]
        dq_ref[0, 0, at, :], dk_ref[0, 0, at, :], dv, dgates_ref[0, 0, t], d_left = _chunk_backward(
            c, m, states, _by_head(sent_ref, at, d), d_left, d)
        for h in range(d.heads):
            dv_ref[0, 0, h, at, :] = _of_head(dv, h, d)
    for h in range(d.heads):
        d_state_ref[h] = d_left[h]


def _specs(d: Dims, blocks: int, reverse: bool):
    """The block specs of a call's operands by kind: a key head's positions (head-major arrays), those of its value
    heads, the gates a chunk and the states a chunk.  ``reverse``: the grid's last axis walks the chunks from the
    last block to the first."""
    block = (lambda c: blocks - 1 - c) if reverse else (lambda c: c)
    positions = d.steps * d.chunk
    return {
        "keys": pl.BlockSpec((1, 1, positions, d.dk), lambda s, n, c: (s, n, block(c), 0)),
        "values": pl.BlockSpec((1, 1, d.heads, positions, d.dv), lambda s, n, c: (s, n, 0, block(c), 0)),
        "gates": pl.BlockSpec((1, 1, d.steps, 2, d.heads * d.chunk), lambda s, n, c: (s, n, block(c), 0, 0)),
        "states": pl.BlockSpec((1, 1, d.steps, d.heads, d.dk, d.dv), lambda s, n, c: (s, n, block(c), 0, 0, 0)),
    }


def _call(kernel, d: Dims, name: str, grid, in_specs, out_specs, out_shape):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d.heads, d.dk, d.dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=d.interpret, name=name)


def _forward(q, k, v, gates, d: Dims, keep: bool):
    sequences, key_heads, chunks = gates.shape[:3]
    blocks = chunks // d.steps
    spec = _specs(d, blocks, reverse=False)
    out = jax.ShapeDtypeStruct(v.shape, jnp.float32)
    entered = jax.ShapeDtypeStruct((sequences, key_heads, chunks, d.heads, d.dk, d.dv), jnp.float32)
    return _call(functools.partial(_forward_kernel, d=d, keep=keep), d, "delta_core_fwd" + ("_keep" if keep else ""),
                 (sequences, key_heads, blocks), [spec["keys"], spec["keys"], spec["values"], spec["gates"]],
                 [spec["values"], spec["states"]] if keep else spec["values"], [out, entered] if keep else out)(q, k, v, gates)


def _backward(q, k, v, gates, entered, sent, d: Dims):
    sequences, key_heads, chunks = gates.shape[:3]
    blocks = chunks // d.steps
    spec = _specs(d, blocks, reverse=True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    return _call(functools.partial(_backward_kernel, d=d), d, "delta_core_bwd", (sequences, key_heads, blocks),
                 [spec["keys"], spec["keys"], spec["values"], spec["gates"], spec["states"], spec["values"]],
                 [spec["keys"], spec["keys"], spec["values"], spec["gates"]], [like(q), like(k), like(v), like(gates)],
                 )(q, k, v, gates, entered, sent)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _whole_chunks(q, k, v, gates, d: Dims):
    """The outputs of whole chunks, head-major: ``q``, ``k`` (sequences, key heads, positions, dk), ``v`` and the
    result (sequences, key heads, value heads a key head, positions, dv), ``gates`` (sequences, key heads, chunks, 2,
    value heads a key head x chunk).  Without a backward pass to follow no state is written."""
    return _forward(q, k, v, gates, d, keep=False)


def _whole_chunks_fwd(q, k, v, gates, d: Dims):
    out, entered = _forward(q, k, v, gates, d, keep=True)
    return out, (q, k, v, gates, entered)


def _whole_chunks_bwd(d: Dims, kept, sent):
    return tuple(_backward(*kept, sent, d))


_whole_chunks.defvjp(_whole_chunks_fwd, _whole_chunks_bwd)


def _pipelined_bytes(d: Dims) -> int:
    """What a grid step's blocks take of fast memory, two buffers each, in the backward kernel (the larger): q, k
    and their cotangents, v, the outputs' cotangent and v's, the states that entered, the gates and theirs."""
    tokens = d.chunk * (4 * d.dk + 3 * d.heads * d.dv)
    return 2 * 4 * d.steps * (tokens + d.heads * d.dk * d.dv + 4 * d.heads * d.chunk)


def _steps(chunks: int, d: Dims) -> int:
    """The chunks a grid step walks: the most, up to ``MAX_STEPS``, that divide ``chunks`` and whose blocks fit."""
    return next(t for t in range(min(MAX_STEPS, chunks), 0, -1)
                if chunks % t == 0 and (t == 1 or _pipelined_bytes(d._replace(steps=t)) <= BLOCK_BYTES))


def fits(dk: int, dv: int, chunk: int, heads: int) -> bool:
    """Whether the compiled kernels take these sizes: a head's columns whole lanes, a chunk whole sublanes, and a
    chunk of a key head's value heads within fast memory (its blocks, and intermediates of rows x rows)."""
    return (dk % 128 == 0 and dv % 128 == 0 and chunk % 8 == 0 and heads * chunk <= MAX_ROWS
            and _pipelined_bytes(Dims(chunk, heads, dk, dv, 1, False)) <= BLOCK_BYTES)


def delta_core(q, k, v, g, beta, chunk: int, interpret: bool = False):
    """:func:`gentun_tpu.models.lfm2_moe._delta_core_xla` (its arguments and result) as the kernels above.  Outside
    them stay the running sum of ``g`` inside a chunk, whose transpose jax writes, and the gates' change of layout:
    2 of a position's 770 floats."""
    s, length, n, dk = q.shape
    r, dv = v.shape[3], v.shape[4]
    pad = -length % chunk
    if pad:  # positions that write nothing: beta = 0, g = 0
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (q, k, v, g, beta))
    chunks = (length + pad) // chunk
    d = Dims(chunk, r, dk, dv, 1, interpret)
    by_chunk = lambda a: a.reshape(s, chunks, chunk, n, r).transpose(0, 3, 1, 4, 2)  # (s, n, chunks, r, chunk)
    gates = jnp.stack([jnp.cumsum(by_chunk(g), axis=-1), by_chunk(beta)], axis=3).reshape(s, n, chunks, 2, r * chunk)
    # head-major operands: a change of layout that XLA:TPU carries to the fusions that make q, k and v and to the one
    # that reads o (whole tiles of 8 positions x 128 columns move), where a merge of the heads' columns is a pass
    out = _whole_chunks(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 3, 1, 4), gates,
                        d._replace(steps=_steps(chunks, d)))
    return out.transpose(0, 3, 1, 2, 4)[:, :length]
