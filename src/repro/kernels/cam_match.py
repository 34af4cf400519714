"""Pallas TPU kernel: index creation (the BIC core's CAM match and TM).

The ASIC's CAM compares one key per cycle against a 32-word record held in
match-line registers, buffers the match bits record-major, and transposes
them key-major in its TM.  On TPU the record is the lane: records sit on
the (8, 128) vreg grid, the key is a scalar splat, and the match bits of
32 records pack into one key-major word in registers, so the kernel writes
the key-major packed index directly and no transpose stage exists.

Relayout (XLA, inside this jit, one pass over the records): the word axis
``g`` of the output (record ``32*g + r`` is bit ``r`` of word ``g``) is
viewed as ``(rows, lanes)``, and ``x[r, i, s, l] = records[32*(s*lanes +
l) + r, i]``.  Then ``x[r, i]`` is a whole ``(rows, lanes)`` slab of
records — bit ``r`` of every output word, record word ``i`` — and for one
key ``m``::

    word[s, l] = OR_r ((OR_i x[r, i, s, l] == key_m) << r)

Each operand is a whole vreg of records; there is no lane broadcast and no
lane reshape.  Keys enter SMEM as scalars, ``KEYS_PER_PASS`` at a time,
each with its accumulator held in registers while one slab of records is
loaded once per record word (the silicon also compares 8 keys per pass).

Block shapes: x (32, W, BR, L) int32 (keys (M,) int32 in SMEM) -> out
(BM, BR, L) u32, with ``L = min(N/32, 128)`` lanes and ``BR`` rows of words
(the whole axis, or a multiple of 8).  The grid runs key blocks innermost,
so each records block is read from HBM once; the records block is 128 KiB
per record word at 8 rows, so wide records raise the kernel's VMEM limit.  :func:`repro.kernels.ops
.create_index` pads arbitrary shapes and picks the blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 32
LANES = 128
SUBLANES = 8
#: keys compared per load of a record slab; their accumulators stay in vregs
KEYS_PER_PASS = 8
#: the TPU compiler's own scoped VMEM limit for a kernel (v5e)
_VMEM_DEFAULT = 16 << 20
_U32 = jnp.uint32


def _cam_match_kernel(keys_ref, x_ref, out_ref):
    """One (BR x L words) x (BM keys) tile; ``keys_ref`` holds all M keys."""
    _, w, br, lanes = x_ref.shape
    bm = out_ref.shape[0]
    sub = min(br, SUBLANES)
    first = pl.program_id(1) * bm

    def key_pass(p, carry):
        base = p * KEYS_PER_PASS
        keys = [keys_ref[first + base + k] for k in range(KEYS_PER_PASS)]

        def slab(t, carry):
            rows = pl.ds(pl.multiple_of(t * sub, sub), sub)

            def bit(r, accs):
                hits = [None] * KEYS_PER_PASS
                for i in range(w):                   # W is static: unrolled
                    v = x_ref[r, i, rows, :]
                    for k in range(KEYS_PER_PASS):
                        h = v == keys[k]
                        hits[k] = h if hits[k] is None else hits[k] | h
                b = _U32(1) << r.astype(_U32)
                return tuple(a | jnp.where(h, b, _U32(0))
                             for a, h in zip(accs, hits))

            zero = jnp.zeros((sub, lanes), _U32)
            accs = jax.lax.fori_loop(0, PACK, bit, (zero,) * KEYS_PER_PASS)
            for k in range(KEYS_PER_PASS):
                out_ref[base + k, rows, :] = accs[k]
            return carry

        return jax.lax.fori_loop(0, br // sub, slab, carry)

    jax.lax.fori_loop(0, bm // KEYS_PER_PASS, key_pass, 0)


@functools.partial(jax.jit, static_argnames=("block_w", "block_m", "interpret"))
def cam_match(records: jax.Array, keys: jax.Array, *,
              block_w: int, block_m: int, interpret: bool) -> jax.Array:
    """records (N, W) int, keys (M,) int32 -> key-major packed index
    (M, N/32) uint32: bit ``n % 32`` of word ``[m, n // 32]`` is set when
    record ``n`` holds key ``m``.

    N % 32 == 0 and N/32 is at most 128 or a multiple of 128; ``block_w``
    (words per tile) divides N/32 and is the whole axis or a multiple of
    8 x 128; ``block_m`` divides M and is a multiple of ``KEYS_PER_PASS``
    (ops.py pads arbitrary shapes and chooses ``interpret`` from the
    platform).
    """
    N, W = records.shape
    (M,) = keys.shape
    assert N % PACK == 0
    nw = N // PACK
    lanes = min(nw, LANES)
    assert nw % lanes == 0 and nw % block_w == 0 and block_w % lanes == 0
    rows, block_r = nw // lanes, block_w // lanes
    assert block_r == rows or block_r % SUBLANES == 0
    assert M % block_m == 0 and block_m % KEYS_PER_PASS == 0
    # x[r, i, s, l] = records[32*(s*lanes + l) + r, i]
    x = (records.astype(jnp.int32).reshape(rows, lanes, PACK, W)
         .transpose(2, 3, 0, 1))
    out = pl.pallas_call(
        _cam_match_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // block_r, M // block_m),
            in_specs=[pl.BlockSpec((PACK, W, block_r, lanes),
                                   lambda s, j, keys: (0, 0, s, 0))],
            out_specs=pl.BlockSpec((block_m, block_r, lanes),
                                   lambda s, j, keys: (j, s, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, rows, lanes), _U32),
        # both blocks double-buffered: wide records outgrow the default
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            _VMEM_DEFAULT, 2 * 4 * block_w * (PACK * W + block_m) + (4 << 20))),
        interpret=interpret,
    )(keys.astype(jnp.int32), x)
    return out.reshape(M, nw)
