"""Bulk-bitwise execution: whole pass programs as tiled multi-word sweeps.

The paper's BIC (and the in-memory bulk-bitwise engines it anticipates —
Buddy-RAM, SiM) wins by treating boolean filtering as a *bulk memory
operation*: AND/OR/NOT over huge bitvectors runs at whatever bandwidth the
memory system sustains, not at dispatch rate.  The ``ref``/``pallas``
backends execute one fused AND pass per call; the batched executor vmaps
those per-pass calls, which streams every operand row end to end — at
serving sizes the augmented index is re-read from far memory once per
literal of every query in the bucket.

This module is the third backend's execution core: it runs the WHOLE
lowered pass program (the ``(Q, G, P, L)`` selector arrays of one bucket,
see :mod:`repro.engine.batch`) as a sweep over *word tiles*:

  * every literal of every query gathers its operand row ONCE; the AND
    over literals, the De-Morgan xor, the AND over passes and the OR over
    groups all fold before the result rows are written — one fused
    multi-word sweep instead of one dispatch per pass;
  * tail masking + popcount run fused over the swept rows;
  * the sweep is memory-bounded, not memory-proportional: on TPU the
    Pallas kernel walks word tiles sized to VMEM (:func:`tile_words`);
    the pure-``jnp`` realization instead chunks the QUERY axis when the
    ``(Q, G, P, Nw)`` accumulator would outgrow :data:`SWEEP_BUDGET_BYTES`
    (word-tiling via ``lax.map`` serializes into per-tile dispatch
    overhead on CPU — query chunks keep whole rows streaming).

Two realizations share that schedule: :func:`run_program` (pure ``jnp`` —
the portable fallback, and the CPU fast path) and the word-tiled Pallas
kernel :func:`repro.kernels.bitmap_ops.bulk_program` (used on TPU).  Both
are bit-identical to the per-pass bucket body; the differential sweep in
``tests/test_backend_sweep.py`` gates that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine import policy
from repro.kernels import bitmap_ops, ops, ref

_U32 = jnp.uint32

#: VMEM budget (bytes) for one word tile of the Pallas sweep: the
#: double-buffered augmented-index input tile plus the double-buffered
#: (Q, T) output tile.  Half of a v5e core's 16 MiB default scoped VMEM
#: limit, which leaves the rest for the rows the kernel holds live.
TILE_BUDGET_BYTES = 8 << 20

#: Floor on the tile width (words): one TPU lane tile.  Below this the
#: per-tile bookkeeping dominates and the sweep degenerates into dispatch
#: overhead.
MIN_TILE_WORDS = 128


def tile_words(m1: int, q: int, nw: int,
               budget: int = TILE_BUDGET_BYTES) -> int:
    """Largest power-of-two word-tile width such that the double-buffered
    augmented index tile (``m1`` rows) and output tile (``q`` rows), each
    padded to 8 sublanes, fit the VMEM budget; never below
    :data:`MIN_TILE_WORDS`, never wider than the (pow2-rounded) row
    itself."""
    rows = 2 * (policy.round_up(m1, 8) + policy.round_up(q, 8))
    t = MIN_TILE_WORDS
    while t < nw:
        t *= 2
    while t > MIN_TILE_WORDS and rows * t * 4 > budget:
        t //= 2
    return t


def query(rows: jax.Array, invert: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``Backend.query`` for the bulk backend: one fused AND-with-inversion
    pass as a single bulk reduction (no per-literal unrolled chain — the
    reduce tree is XLA's to schedule at memory speed).  Same contract as
    :func:`repro.kernels.ref.bitmap_query`: tail bits are NOT masked."""
    flips = invert.astype(_U32)[:, None] * _U32(0xFFFFFFFF)
    result = jax.lax.reduce(rows ^ flips, _U32(0xFFFFFFFF),
                            jax.lax.bitwise_and, (0,))
    count = jax.lax.population_count(result).astype(jnp.int32).sum()
    return result, count


def create_index(records: jax.Array, keys: jax.Array) -> jax.Array:
    """Index creation is already one bulk pass (vectorized match +
    transpose); the bulk backend shares the oracle pipeline — its win is
    the query side."""
    n = records.shape[0]
    m = keys.shape[0]
    packed = ref.create_index(policy.pad_records(records),
                              policy.pad_keys(keys))
    return packed[:m, : policy.num_words(n)]


#: Cap on the pure-jnp sweep's largest intermediate — the (Qc, G, P, Nw)
#: accumulator of one query chunk.  Above it the query axis chunks via
#: ``lax.map``; whole rows keep streaming either way.
SWEEP_BUDGET_BYTES = 64 << 20


def _sweep_block(aug, sels, invs, post, flip):
    """One fused sweep over full rows: sels/invs/post carry a leading
    query-chunk axis; returns (Qc, Nw) result rows, tails unmasked."""
    q, g, p, l = sels.shape
    acc = None
    for li in range(l):                       # static unroll: bucket L
        opnd = jnp.take(aug, sels[..., li], axis=0)       # (q, g, p, Nw)
        x = opnd ^ flip[..., li, None]
        acc = x if acc is None else acc & x
    acc = acc ^ post[..., None]               # De-Morgan OR-pass mask
    grp = acc[:, :, 0]
    for pi in range(1, p):
        grp = grp & acc[:, :, pi]
    out = grp[:, 0]
    for gi in range(1, g):
        out = out | grp[:, gi]
    return out                                # (q, Nw)


def _sweep_jnp(aug: jax.Array, sels: jax.Array, invs: jax.Array,
               post: jax.Array) -> jax.Array:
    """The fused sweep, pure jnp: aug (M+1, Nw) augmented packed index,
    sels/invs (Q, G, P, L), post (Q, G, P) xor masks -> rows (Q, Nw),
    tail bits NOT yet masked.  Query-chunked past the accumulator
    budget; bit-identical either way."""
    m1, nw = aug.shape
    q, g, p, l = sels.shape
    flip = invs.astype(_U32) * _U32(0xFFFFFFFF)
    per_query = g * p * max(nw, 1) * 4
    qc = max(1, SWEEP_BUDGET_BYTES // max(per_query, 1))
    if qc >= q:
        return _sweep_block(aug, sels, invs, post, flip)
    while q % qc:                             # q is a power of two
        qc -= 1
    chunk = lambda a: a.reshape((q // qc, qc) + a.shape[1:])  # noqa: E731
    swept = jax.lax.map(
        lambda args: _sweep_block(aug, *args),
        (chunk(sels), chunk(invs), chunk(post), chunk(flip)))
    return swept.reshape(q, nw)


def run_program(aug: jax.Array, num_records, sels: jax.Array,
                invs: jax.Array, post: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Whole-bucket executor (the ``Backend.run_program`` hook): identical
    call contract to the per-pass bucket body in :mod:`repro.engine.batch`
    — aug (M+1, Nw) with the all-ones identity row at M, selector arrays
    (Q, G, P, L), post xor masks (Q, G, P) — returning (rows (Q, Nw),
    counts (Q,)) with tails masked past ``num_records``.

    On TPU the sweep runs as the word-tiled Pallas kernel; elsewhere as
    the pure-jnp tiled sweep.  Uncompiled — the batch layer jits (and
    vmaps, for segment stacks) exactly like the per-pass body.
    """
    if ops.interpret_mode():
        rows = _sweep_jnp(aug, sels, invs, post)
        return jax.vmap(policy.mask_tail, in_axes=(0, None))(rows,
                                                              num_records)
    return run_program_pallas(aug, num_records, sels, invs, post)


def run_program_pallas(aug: jax.Array, num_records, sels: jax.Array,
                       invs: jax.Array, post: jax.Array, *,
                       block_n: int | None = None,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """The Pallas realization, callable explicitly (tests exercise it in
    interpret mode off-TPU; :func:`run_program` routes to it on TPU)."""
    if interpret is None:
        interpret = ops.interpret_mode()
    if block_n is None:
        block_n = tile_words(aug.shape[0], sels.shape[0], aug.shape[1])
    rows = bitmap_ops.bulk_program(aug, sels, invs, post, block_n=block_n,
                                   interpret=interpret)
    return jax.vmap(policy.mask_tail, in_axes=(0, None))(rows, num_records)
