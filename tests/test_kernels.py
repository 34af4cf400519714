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
def _want_index(records, keys):
    """Key-major oracle for any shape: pad with the canonical sentinels,
    run ``ref.create_index``, slice back."""
    n, m = records.shape[0], keys.shape[0]
    packed = ref.create_index(ref.pad_records(records), ref.pad_keys(keys))
    return np.asarray(packed[:m, :ref.num_words(n)])


def _sparse_keys(n, w, m):
    """Keys neither sorted nor dense; records drawn from the keys and from
    values that are no key."""
    keys = RNG.choice(1 << 20, m, replace=False).astype(np.int32)
    pool = np.concatenate([keys, RNG.integers(-5, 1 << 21, m)]).astype(np.int32)
    return RNG.choice(pool, (n, w)), keys


def _sentinels(n, w, m):
    """Keys 0..M-1; records hold the record pad sentinel, the key pad
    sentinel and words at or past M."""
    keys = np.arange(m, dtype=np.int32)
    records = RNG.integers(0, 2 * m, (n, w)).astype(np.int32)
    records[RNG.random((n, w)) < 0.2] = ref.RECORD_SENTINEL
    records[RNG.random((n, w)) < 0.1] = ref.KEY_SENTINEL
    return records, keys


@pytest.mark.parametrize("n,w,m,bw,bm", [
    (32, 32, 32, 1, 32),          # paper-like core geometry, one word
    (64, 8, 64, 2, 16),           # several key blocks
    (4096, 32, 128, 128, 64),     # one whole vreg row of 128 words
    (12288, 16, 256, 384, 128),   # whole axis of 3 rows (not a multiple of 8)
    (65536, 4, 32, 1024, 8),      # two blocks of 8 x 128 words
    (2048, 16, 48, 64, 24),       # key count not a multiple of 32
])
def test_cam_match_kernel_shapes(n, w, m, bw, bm):
    records, keys = (jnp.asarray(a) for a in _sparse_keys(n, w, m))
    got = cam_match(records, keys, block_w=bw, block_m=bm, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), _want_index(records, keys))


@pytest.mark.parametrize("make,n,w,m", [
    (_sparse_keys, 64, 32, 64),                                  # (a)
    (_sparse_keys, 4100, 6, 40),                                 # (a)+(b)
    (_sparse_keys, 77, 5, 9),                                    # (b)
    (lambda n, w, m: (RNG.integers(0, m, (n, w)).astype(np.int32),
                      RNG.permutation(m).astype(np.int32)),
     300, 14, 1795),                                              # (c) SSB
    (_sentinels, 200, 8, 64),                                    # (d)
], ids=["unsorted-sparse-keys", "ragged-n-past-a-row",
        "ragged-n", "ssb-widths", "sentinels-and-out-of-range"])
def test_create_index_cases(make, n, w, m):
    records, keys = (jnp.asarray(a) for a in make(n, w, m))
    got = ops.create_index(records, keys)
    np.testing.assert_array_equal(np.asarray(got), _want_index(records, keys))


@pytest.mark.parametrize("raw", [False, True], ids=["ops", "kernel"])
def test_create_index_batches_under_vmap(raw):
    """(e) a leading block axis, as ``multicore_create_index`` maps it."""
    blocks = jnp.asarray(RNG.integers(0, 40, (3, 96, 7), dtype=np.int32))
    keys = jnp.asarray(RNG.permutation(40)[:32].astype(np.int32))
    if raw:
        fn = lambda r: cam_match(r, keys, block_w=3, block_m=16,
                                 interpret=True)
    else:
        fn = lambda r: ops.create_index(r, keys)
    got = np.asarray(jax.vmap(fn)(blocks))
    for b in range(blocks.shape[0]):
        np.testing.assert_array_equal(got[b], _want_index(blocks[b], keys))


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
