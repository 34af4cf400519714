"""Public entry points for the BIC Pallas kernels.

These wrappers accept arbitrary shapes (padding to kernel tile multiples),
pick block sizes that satisfy the TPU's (8, 128) tiling rule, and choose
interpret mode in one place (:func:`interpret_mode`): on TPU the kernels
compile to Mosaic; on any other platform they run through the Pallas
interpreter (bit-exact, used by the test suite).  The raw kernels take
``interpret`` as a required argument.  ``ref.py`` holds the pure-jnp
oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bit_transpose as _bt
from repro.kernels import bitmap_ops as _bq
from repro.kernels import cam_match as _cm
from repro.kernels import ref
# The canonical padding/sentinel policy lives with the packing conventions
# in ref.py; these wrappers only add kernel-specific block alignment.
from repro.kernels.ref import PACK, pad_keys, pad_records
from repro.kernels.ref import round_up as _round_up


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: never on a TPU, always
    elsewhere.  The one place that decision is made."""
    return jax.default_backend() != "tpu"


def _pick_block(total: int, preferred: int, multiple: int) -> int:
    """Largest divisor-friendly block: min(preferred, total), multiple-aligned."""
    b = min(preferred, total)
    b = max(multiple, b - (b % multiple))
    while total % b:
        b -= multiple
    return b


def _tiled_block(total: int, block: int) -> tuple[int, int]:
    """(block, padded total) for an axis the TPU tiles: the whole axis when
    it fits in one ``block`` (a full-extent block is always legal), else
    ``block`` — a multiple of the tile — with the axis padded to fit."""
    if total <= block:
        return max(total, 1), total
    return block, _round_up(total, block)


def cam_match(records: jax.Array, keys: jax.Array, *,
              interpret: bool | None = None) -> jax.Array:
    """records (N, W) int, keys (M,) int -> packed (N, ceil(M/32)) uint32,
    record-major: the key-major index of :func:`create_index` through
    :func:`transpose` (padded keys read as keys no record holds)."""
    n = records.shape[0]
    key_major = create_index(records, keys, interpret=interpret)
    return transpose(key_major, interpret=interpret)[:n]


def transpose(packed: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Packed (R, Cw) uint32 -> (Cw*32, ceil(R/32)) uint32 (zero-padded R)."""
    if interpret is None:
        interpret = interpret_mode()
    R, Cw = packed.shape
    rw = -(-R // PACK)
    block_c, cwp = _tiled_block(Cw, 8)          # sublane axis of the slabs
    block_g, rwp = _tiled_block(rw, 256)        # lane axis of the slabs
    x = jnp.pad(packed.astype(jnp.uint32),
                ((0, rwp * PACK - R), (0, cwp - Cw)))
    out = _bt.bit_transpose(x, block_c=block_c, block_g=block_g,
                            interpret=interpret)
    return out[:Cw * PACK, :rw]


def query(rows: jax.Array, invert: jax.Array, *,
          interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Fused AND_k (invert_k ? ~row_k : row_k) + popcount over packed rows.

    rows (K, Nw) uint32.  NOTE: inverted rows make the *padding* words all-1s;
    we therefore mask padded words back to zero before the popcount by
    padding every row with 0 and additionally ANDing an all-ones literal row
    is unnecessary — instead we pad with a non-inverted all-zero row, which
    forces padded result words to 0 regardless of inversions.
    """
    if interpret is None:
        interpret = interpret_mode()
    K, Nw = rows.shape
    block_n, Nwp = _tiled_block(Nw, 2048)
    pad_cols = Nwp - Nw
    r = jnp.pad(rows.astype(jnp.uint32), ((0, 0), (0, pad_cols)))
    inv = invert.astype(jnp.int32)
    if pad_cols and bool(K):
        # Guard: if every operand is inverted, padded words become all-ones.
        # Append one non-inverted row that is all-ones in the real region and
        # zero in the pad, restoring correctness without branching.
        guard = jnp.concatenate([
            jnp.full((1, Nw), 0xFFFFFFFF, dtype=jnp.uint32),
            jnp.zeros((1, pad_cols), dtype=jnp.uint32)], axis=1)
        r = jnp.concatenate([r, guard], axis=0)
        inv = jnp.concatenate([inv, jnp.zeros((1,), jnp.int32)])
    result, count = _bq.bitmap_query(r, inv, block_n=block_n,
                                     interpret=interpret)
    return result[:Nw], count


def _create_blocks(n: int, m: int) -> tuple[int, int, int, int]:
    """(block_w, padded N, block_m, padded M) for :func:`create_index`.

    The word axis (ceil(N/32)) is whole when it fits one vreg row of 128
    lanes; past that it is rows of 128 words, whole up to 8 rows, else in
    blocks of 8 rows.  Keys pad to ``KEYS_PER_PASS`` and block by up to
    256, so the output block stays within 1 MiB."""
    nw = -(-n // PACK)
    if nw > _cm.LANES:
        rows = -(-nw // _cm.LANES)
        block_r, rows = _tiled_block(rows, _cm.SUBLANES)
        nw, block_w = rows * _cm.LANES, block_r * _cm.LANES
    else:
        block_w = max(nw, 1)
    mp = _round_up(max(m, 1), _cm.KEYS_PER_PASS)
    block_m = _pick_block(mp, 256, _cm.KEYS_PER_PASS)
    return block_w, max(nw, 1) * PACK, block_m, mp


def create_index(records: jax.Array, keys: jax.Array, *,
                 interpret: bool | None = None) -> jax.Array:
    """Index creation: records (N, W), keys (M,) -> key-major packed
    bitmap (M, ceil(N/32)) uint32.

    One kernel (:func:`repro.kernels.cam_match.cam_match`) matches records
    against keys and writes key-major words, the kernel realization of
    Fig. 3 of the paper.  Records pad with the record sentinel and keys
    with the key sentinel, so padding matches nothing; equals
    ``ref.create_index`` on 32-aligned shapes.  Records of any integer
    dtype are widened to int32 on the device: inside the kernel's jit
    when they need no pad rows, else by the pad.
    """
    if interpret is None:
        interpret = interpret_mode()
    n = records.shape[0]
    (m,) = keys.shape
    block_w, np_, block_m, mp = _create_blocks(n, m)
    if np_ != n:
        records = pad_records(records, np_)
    out = _cm.cam_match(records, pad_keys(keys, mp),
                        block_w=block_w, block_m=block_m,
                        interpret=interpret)
    return out[:m, :-(-n // PACK)]


__all__ = ["cam_match", "transpose", "query", "create_index", "ref"]
