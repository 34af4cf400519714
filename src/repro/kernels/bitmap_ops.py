"""Pallas TPU kernels: fused bitmap query execution.

The point of a bitmap index is that a multi-dimensional query like
"A2 AND A4 AND (NOT A5)" is a streaming pass over K packed index rows.
Done naively that is K-1 separate elementwise passes (2(K-1) reads +
K-1 writes of the row length); the fused kernel reads each operand row
once, folds the masked AND in VMEM and emits both the result row and its
popcount (selectivity) in a single pass — the TPU analogue of the ASIC
streaming the BI rows through a logic tree.

rows (K, Nw) uint32, invert (K,) int32 -> (result (Nw,), count ()).
The inversion flags are scalar-prefetched into SMEM; the popcount
accumulates in an SMEM output across a sequential grid.

:func:`bulk_program` extends the same idea to a whole bucket of lowered
pass programs (the bulk backend's TPU path, see :mod:`repro.engine.bulk`):
the grid walks word tiles of the augmented index; per tile, every literal
of every query reads its operand row from the VMEM-resident tile at a
scalar-prefetched row index, and the full AND-over-literals / xor /
AND-over-passes / OR-over-groups tree folds before one write of the
tile's result words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_U32 = jnp.uint32


def _flip(row: jax.Array, flag) -> jax.Array:
    """``~row`` where the scalar ``flag`` is set, else ``row``."""
    return jnp.where(flag != 0, ~row, row)


def _query_kernel(invert_ref, rows_ref, out_ref, count_ref):
    result = None
    for k in range(rows_ref.shape[0]):          # K is static: unrolled
        term = _flip(rows_ref[k:k + 1, :], invert_ref[k])
        result = term if result is None else result & term
    out_ref[...] = result

    @pl.when(pl.program_id(0) == 0)
    def _init():
        count_ref[0, 0] = 0

    count_ref[0, 0] += jnp.sum(
        jax.lax.population_count(result).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def bitmap_query(rows: jax.Array, invert: jax.Array, *,
                 block_n: int, interpret: bool
                 ) -> tuple[jax.Array, jax.Array]:
    """AND_k (invert_k ? ~rows_k : rows_k) with fused popcount.

    rows (K, Nw) uint32, invert (K,) int -> (result (Nw,) uint32, count int32).
    Nw % block_n == 0 (ops.py pads and chooses ``interpret``).
    """
    K, Nw = rows.shape
    assert Nw % block_n == 0
    result, count = pl.pallas_call(
        _query_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                   # invert -> SMEM
            grid=(Nw // block_n,),
            in_specs=[pl.BlockSpec((K, block_n), lambda i, inv: (0, i))],
            out_specs=[
                pl.BlockSpec((1, block_n), lambda i, inv: (0, i)),
                pl.BlockSpec((1, 1), lambda i, inv: (0, 0),
                             memory_space=pltpu.SMEM),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, Nw), _U32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        # the popcount accumulates across word tiles: sequential grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(invert.astype(jnp.int32), rows.astype(_U32))
    return result[0], count[0, 0]


def _bulk_kernel(sels_ref, invs_ref, post_ref, aug_ref, out_ref, *,
                 g: int, p: int, l: int):
    """One word tile: every query of the bucket, one result row each."""

    def one_query(qi, carry):
        out = None
        for gi in range(g):                     # G, P, L static: unrolled
            grp = None
            for pi in range(p):
                slot = (qi * g + gi) * p + pi
                acc = None
                for li in range(l):
                    s = slot * l + li
                    row = aug_ref[pl.ds(sels_ref[s], 1), :]   # (1, BN)
                    term = _flip(row, invs_ref[s])
                    acc = term if acc is None else acc & term
                acc = _flip(acc, post_ref[slot])  # De-Morgan OR-pass mask
                grp = acc if grp is None else grp & acc
            out = grp if out is None else out | grp
        out_ref[pl.ds(qi, 1), :] = out
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], one_query, 0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def bulk_program(aug: jax.Array, sels: jax.Array, invs: jax.Array,
                 post: jax.Array, *, block_n: int,
                 interpret: bool) -> jax.Array:
    """Whole-bucket bulk sweep: aug (M+1, Nw) uint32 augmented packed
    index (all-ones identity row at M), sels/invs (Q, G, P, L) selector/
    inversion arrays, post (Q, G, P) uint32 xor masks (0 or all-ones)
    -> rows (Q, Nw).

    Result = OR over groups of [AND over passes of [(AND over literals of
    possibly-inverted gathered rows) ^ post]].  Tail bits past the logical
    record count are NOT masked here (the engine masks once per plan).
    The word axis pads to ``block_n`` with zero words — padded selector
    gathers read zeros and the extra columns are sliced off.  Selectors,
    inversions and post masks are scalar-prefetched into SMEM.
    """
    m1, nw = aug.shape
    q, g, p, l = sels.shape
    nwp = -(-nw // block_n) * block_n
    augp = jnp.pad(aug.astype(_U32), ((0, 0), (0, nwp - nw)))
    rows = pl.pallas_call(
        functools.partial(_bulk_kernel, g=g, p=p, l=l),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                   # sels, invs, post
            grid=(nwp // block_n,),
            in_specs=[pl.BlockSpec((m1, block_n),
                                   lambda i, *_: (0, i))],
            out_specs=pl.BlockSpec((q, block_n), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((q, nwp), _U32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(sels.astype(jnp.int32).reshape(-1), invs.astype(jnp.int32).reshape(-1),
      (post != 0).astype(jnp.int32).reshape(-1), augp)
    return rows[:, :nw]
