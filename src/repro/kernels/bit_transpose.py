"""Pallas TPU kernel: Transpose-Matrix (TM) stage of the BIC core.

The ASIC's TM swaps buffer rows into BI columns with a wire permutation.
With bits packed 32-per-uint32 (LSB-first, ref.py) the TPU analogue is a
*bit-block* transpose: every aligned 32x32 bit tile is transposed in-register
with a 5-round butterfly (Hacker's Delight 7-7), then tiles are permuted.
No unpack to bytes ever happens, so VMEM/HBM traffic stays at 1 bit/bit.

The tile permutation is word-granular, so it runs as XLA transposes around
the kernel: the input enters as ``(32, C/32, R/32)`` — row ``i`` of every
32-row group on the leading axis — and the output leaves as ``(32, C/32,
R/32)`` with the transposed word ``j`` on the leading axis.  Inside the
kernel each of the 32 butterfly rows is then a whole lane-dense
``(BC, BG)`` slab: a round combines slab ``k`` with slab ``k ^ j`` by masked
shifts, with no sublane shuffles or in-register transposes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PACK = 32
_U32 = jnp.uint32

# Butterfly rounds (plain ints — jnp constants are built inside the trace,
# Pallas rejects captured array consts): round j swaps the high-j bit-half of
# each "up" row (index bit j clear) with the low-j half of its partner.
_ROUNDS = (
    (16, 0x0000FFFF),
    (8, 0x00FF00FF),
    (4, 0x0F0F0F0F),
    (2, 0x33333333),
    (1, 0x55555555),
)


def _transpose32(rows: list) -> list:
    """Transpose 32x32 bit tiles held as 32 same-shape uint32 slabs.

    LSB-first convention: output slab b bit r == input slab r bit b.
    """
    rows = list(rows)
    for j, mi in _ROUNDS:
        m = _U32(mi)
        ju = _U32(j)
        for k in range(PACK):
            if k & j:
                continue
            t = ((rows[k] >> ju) ^ rows[k + j]) & m
            rows[k] = rows[k] ^ (t << ju)
            rows[k + j] = rows[k + j] ^ t
    return rows


def _bit_transpose_kernel(in_ref, out_ref):
    rows = _transpose32([in_ref[i] for i in range(PACK)])   # 32 x (BC, BG)
    for j in range(PACK):
        out_ref[j] = rows[j]


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_g", "interpret"))
def bit_transpose(packed: jax.Array, *, block_c: int, interpret: bool,
                  block_g: int | None = None) -> jax.Array:
    """Packed (R, C/32) uint32 -> packed (C, R/32) uint32.

    R % 32 == 0, (C/32) % block_c == 0 and (R/32) % block_g == 0
    (``block_g=None`` takes the whole record-word axis; ops.py pads
    arbitrary shapes and chooses ``interpret`` from the platform).
    """
    R, Cw = packed.shape
    assert R % PACK == 0 and Cw % block_c == 0
    Rw = R // PACK
    block_g = Rw if block_g is None else block_g
    assert Rw % block_g == 0
    # x[i, cw, g] = packed[g*32 + i, cw]
    x = packed.astype(_U32).reshape(Rw, PACK, Cw).transpose(1, 2, 0)
    spec = pl.BlockSpec((PACK, block_c, block_g), lambda c, g: (0, c, g))
    y = pl.pallas_call(
        _bit_transpose_kernel,
        grid=(Cw // block_c, Rw // block_g),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((PACK, Cw, Rw), _U32),
        interpret=interpret,
    )(x)
    # y[j, cw, g] is output row cw*32 + j, word g
    return y.transpose(1, 0, 2).reshape(Cw * PACK, Rw)
