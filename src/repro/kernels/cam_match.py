"""Pallas TPU kernel: CAM match stage of the BIC core.

The ASIC's CAM compares one key per cycle against a 32-word record held in
match-line registers.  On TPU the analogue of the parallel match lines is the
VPU lane grid: we tile BN records x BM keys into VMEM, broadcast each record
word across lanes and OR-reduce the per-word equality over the record-word
axis.  Match bits never leave VMEM unpacked — they are packed 32-per-uint32
before the store, which is the TPU analogue of the paper's register-file
buffer (and cuts HBM write traffic by 32x).

Packing without a lane reshape: the keys enter bit-major, ``keys_t[b, 0, j]
= keys[j*32 + b]``, so bit plane ``b`` of every output word is one
lane-dense ``(BN, BM/32)`` compare-and-OR against key row ``b`` — the pack
is a shift-OR into the accumulator, never a split of the lane axis.

Block shapes: records (BN, W) int32, keys_t (32, 1, BM/32) int32 -> out
(BN, BM/32) u32.  On TPU the lane dim of the output block (BM/32) must be a
multiple of 128 or the whole key-word axis; :func:`repro.kernels.ops
.cam_match` picks such blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PACK = 32
_U32 = jnp.uint32


def _cam_match_kernel(records_ref, keys_ref, out_ref):
    """One (BN records) x (BM keys) tile."""
    bn, w = records_ref.shape
    bmw = out_ref.shape[1]

    def bit_plane(b, acc):
        key_row = keys_ref[b]                        # (1, BM/32) int32
        match = jnp.zeros((bn, bmw), jnp.bool_)
        for i in range(w):                           # W is static: unrolled
            match = match | (records_ref[:, i:i + 1] == key_row)
        return acc | jnp.where(match, _U32(1) << b.astype(_U32), _U32(0))

    out_ref[...] = jax.lax.fori_loop(0, PACK, bit_plane,
                                     jnp.zeros((bn, bmw), _U32))


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def cam_match(records: jax.Array, keys: jax.Array, *,
              block_n: int, block_m: int, interpret: bool) -> jax.Array:
    """records (N, W) int32, keys (M,) int32 -> packed (N, M/32) uint32.

    N % block_n == 0, M % block_m == 0, block_m % 32 == 0 (wrappers in
    ops.py pad arbitrary shapes and choose ``interpret`` from the platform).
    """
    N, W = records.shape
    (M,) = keys.shape
    assert M % block_m == 0 and N % block_n == 0 and block_m % PACK == 0
    keys_t = keys.astype(jnp.int32).reshape(M // PACK, PACK).T[:, None, :]
    bmw = block_m // PACK
    return pl.pallas_call(
        _cam_match_kernel,
        grid=(N // block_n, M // block_m),
        in_specs=[
            pl.BlockSpec((block_n, W), lambda i, j: (i, 0)),
            pl.BlockSpec((PACK, 1, bmw), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, bmw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, M // PACK), _U32),
        interpret=interpret,
    )(records.astype(jnp.int32), keys_t)
