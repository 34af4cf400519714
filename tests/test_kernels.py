"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle,
across shapes and dtypes.  The hypothesis property tests on the bit-level
invariants live in tests/test_kernels_properties.py (they skip when
hypothesis is absent; these differential tests never do)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bit_transpose import bit_transpose
from repro.kernels.bitmap_ops import bitmap_query
from repro.kernels.cam_match import cam_match

RNG = np.random.default_rng(42)


# ------------------------------------------------------------- cam_match
@pytest.mark.parametrize("n,w,m,bn,bm", [
    (8, 32, 32, 4, 32),          # paper-like core geometry
    (16, 8, 64, 8, 32),
    (64, 32, 128, 16, 64),
    (256, 16, 256, 64, 128),
])
def test_cam_match_kernel_shapes(n, w, m, bn, bm):
    records = jnp.asarray(RNG.integers(0, 256, (n, w), dtype=np.int32))
    keys = jnp.asarray(RNG.integers(0, 256, (m,), dtype=np.int32))
    got = cam_match(records, keys, block_n=bn, block_m=bm, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.cam_match(records, keys)))


@pytest.mark.parametrize("dtype", [np.int32, np.uint8, np.int16])
def test_cam_match_dtypes(dtype):
    records = jnp.asarray(RNG.integers(0, 120, (16, 8)).astype(dtype))
    keys = jnp.asarray(RNG.integers(0, 120, (32,)).astype(dtype))
    got = ops.cam_match(records, keys)
    want = ref.cam_match(records.astype(jnp.int32), keys.astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cam_match_odd_shapes_padding():
    records = jnp.asarray(RNG.integers(0, 256, (19, 7), dtype=np.int32))
    keys = jnp.asarray(RNG.integers(0, 256, (37,), dtype=np.int32))
    got = ops.cam_match(records, keys)
    dense = np.asarray(ref.cam_match_unpacked(records, keys))
    got_dense = np.asarray(ref.unpack_bits(got, 37))
    np.testing.assert_array_equal(got_dense, dense)


# --------------------------------------------------------- bit_transpose
@pytest.mark.parametrize("r,cw,bc", [
    (32, 1, 1), (64, 4, 2), (128, 8, 8), (256, 16, 4),
])
def test_bit_transpose_kernel(r, cw, bc):
    x = jnp.asarray(RNG.integers(0, 2 ** 32, (r, cw), dtype=np.uint32))
    got = bit_transpose(x, block_c=bc, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.bit_transpose(x)))


# ----------------------------------------------------------- bitmap query
@pytest.mark.parametrize("k,nw,bn", [(1, 8, 8), (3, 64, 32), (5, 256, 128)])
def test_bitmap_query_kernel(k, nw, bn):
    rows = jnp.asarray(RNG.integers(0, 2 ** 32, (k, nw), dtype=np.uint32))
    inv = jnp.asarray(RNG.integers(0, 2, (k,), dtype=np.int32))
    res, cnt = bitmap_query(rows, inv, block_n=bn, interpret=True)
    wres, wcnt = ref.bitmap_query(rows, inv)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(wres))
    assert int(cnt) == int(wcnt)


# -------------------------------------------------- pallas flash attention
@pytest.mark.parametrize("causal,s,bq,bk", [
    (True, 256, 64, 64), (False, 300, 64, 96), (True, 128, 128, 32),
])
def test_pallas_flash_fwd_vs_naive(causal, s, bq, bk):
    from repro.kernels.attention import flash_attention_fwd
    rng = np.random.default_rng(1)
    BH, hd = 3, 32
    q = jnp.asarray(rng.standard_normal((BH, s, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, s, hd)), jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq, block_k=bk)
    scores = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(hd)
    if causal:
        mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(mask[None], scores, -1e30)
    want = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
