"""Hypothesis property tests for the store substrate: serialization
round-trips and the WAL-replay recovery invariant over arbitrary block
streams.  Skips entirely when hypothesis is absent (same policy as
tests/test_kernels_properties.py)."""
import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.engine import backends, policy  # noqa: E402
from repro.engine.runtime import StreamingIndexer  # noqa: E402
from repro.store import SegmentStore  # noqa: E402
from repro.store import format as fmt  # noqa: E402

_DTYPES = [np.uint32, np.int32, np.float32, np.uint8]


@settings(max_examples=25, deadline=None)
@given(n_arrays=st.integers(1, 3), seed=st.integers(0, 2 ** 31 - 1))
def test_array_file_roundtrip_property(n_arrays, seed, tmp_path_factory):
    """Property: write_array_file . read_array_file is the identity on
    arbitrary named array sets (dtype, shape, and bytes all survive)."""
    rng = np.random.default_rng(seed)
    arrays = {}
    for i in range(n_arrays):
        dt = _DTYPES[int(rng.integers(0, len(_DTYPES)))]
        shape = tuple(int(s) for s in rng.integers(0, 9, rng.integers(1, 4)))
        arrays[f"a{i}"] = (rng.integers(0, 255, shape).astype(dt)
                           if dt != np.float32
                           else rng.random(shape, dtype=np.float32))
    path = str(tmp_path_factory.mktemp("af") / "x.seg")
    meta = {"seed": int(seed)}
    fmt.write_array_file(path, arrays, meta=meta)
    out, got_meta = fmt.read_array_file(path)
    assert got_meta == meta
    assert set(out) == set(arrays)
    for k, v in arrays.items():
        assert out[k].dtype == v.dtype and out[k].shape == v.shape
        np.testing.assert_array_equal(out[k], v)


@settings(max_examples=15, deadline=None)
@given(block_sizes=st.lists(st.integers(1, 60), min_size=1, max_size=6),
       flush=st.integers(1, 80), seed=st.integers(0, 2 ** 31 - 1))
def test_spill_recover_roundtrip_property(block_sizes, flush, seed,
                                          tmp_path_factory):
    """Property: for ANY block-size stream and ANY flush threshold, a
    recovered index (segments + WAL replay) is word-for-word identical to
    the never-spilled in-memory index."""
    rng = np.random.default_rng(seed)
    m, w = 7, 3
    keys = jnp.asarray(rng.integers(0, 32, (m,), dtype=np.int32))
    root = str(tmp_path_factory.mktemp("st"))
    si = StreamingIndexer(keys, backend="ref")
    si.attach_store(SegmentStore(root), flush_records=flush)
    blocks = []
    for n in block_sizes:
        blk = jnp.asarray(rng.integers(0, 32, (n, w), dtype=np.int32))
        blocks.append(blk)
        si.append(blk)
    want = backends.get_backend("ref").create_index(
        jnp.concatenate(blocks, axis=0), keys)
    si2 = StreamingIndexer.restore(SegmentStore(root), keys, backend="ref")
    assert si2.num_records == sum(block_sizes)
    np.testing.assert_array_equal(np.asarray(si2.index.packed),
                                  np.asarray(want))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 130), st.integers(1, 100), st.integers(0, 2 ** 31 - 1))
def test_extract_packed_roundtrip_property(start, count, seed):
    """Property: extract_packed reads back exactly the bits splice_packed
    wrote, at any unaligned offset, with a zeroed tail."""
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    m = 3
    total = start + count + int(rng.integers(0, 40))
    bits = rng.integers(0, 2, (m, total)).astype(np.uint32)
    pad = -total % 32
    packed = jnp.asarray(ref.pack_bits(
        jnp.asarray(np.pad(bits, ((0, 0), (0, pad))))))
    got = policy.extract_packed(packed, start, count)
    assert got.shape == (m, -(-count // 32))
    dense = np.asarray(ref.unpack_bits(got, count))
    np.testing.assert_array_equal(dense, bits[:, start:start + count])
    tail = np.asarray(ref.unpack_bits(got, got.shape[1] * 32))
    assert tail[:, count:].sum() == 0
