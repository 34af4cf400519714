"""Compile-only guard: the Pallas kernels of the main path, and the two
query executors built from them, compile for a TPU v5e chip at the widths
``chip_smoke.py`` runs — 2^18-record ingest blocks against 1,024 keys of
32-word records, and 262,144-word key rows (8,388,608 records).

The chip is described, not attached (``jax.experimental.topologies``), so
these tests run on the CPU and say nothing about results or times; they
catch what the chip's compiler refuses (tiling, memory spaces, VMEM) before
any chip run.  The topology is described inside a fixture — never at
import — and every test skips where it cannot be described (no libtpu).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import bulk
from repro.kernels import bit_transpose as bt
from repro.kernels import bitmap_ops as bq
from repro.kernels import cam_match as cm
from repro.kernels import ops

BLOCK = 1 << 18          # records per ingest block
W = 32                   # words per record (the paper's record width)
M = 1024                 # key rows: 32 columns x 32 values
NW = 1 << 18             # words per key row: 2^23 records / 32
Q, G, P, L = 64, 4, 1, 2  # one coalesced bucket of the seven-family mix


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; returns the HLO text.  Raises what
    the chip's compiler raises."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(hlo: str) -> int:
    return hlo.count("tpu_custom_call")


def test_cam_match_compiles_for_v5e(one_chip):
    hlo = _compile(
        functools.partial(cm.cam_match, block_w=8 * 128, block_m=256,
                          interpret=False),
        _spec((BLOCK, W), jnp.int32, one_chip),
        _spec((M,), jnp.int32, one_chip))
    assert _kernels(hlo) == 1


def test_bit_transpose_compiles_for_v5e(one_chip):
    hlo = _compile(
        functools.partial(bt.bit_transpose, block_c=8, block_g=256,
                          interpret=False),
        _spec((BLOCK, M // 32), jnp.uint32, one_chip))
    assert _kernels(hlo) == 1


def test_bitmap_query_compiles_for_v5e(one_chip):
    hlo = _compile(
        functools.partial(bq.bitmap_query, block_n=2048, interpret=False),
        _spec((L, NW), jnp.uint32, one_chip),
        _spec((L,), jnp.int32, one_chip))
    assert _kernels(hlo) == 1


def test_bulk_program_compiles_for_v5e(one_chip):
    hlo = _compile(
        functools.partial(bq.bulk_program,
                          block_n=bulk.tile_words(M + 1, Q, NW),
                          interpret=False),
        _spec((M + 1, NW), jnp.uint32, one_chip),
        _spec((Q, G, P, L), jnp.int32, one_chip),
        _spec((Q, G, P, L), jnp.int32, one_chip),
        _spec((Q, G, P), jnp.uint32, one_chip))
    assert _kernels(hlo) == 1


def test_create_index_compiles_for_v5e(one_chip):
    """The whole ingest pipeline (pad, relayout, match to key-major words,
    slice): one kernel, no bit transpose."""
    hlo = _compile(functools.partial(ops.create_index, interpret=False),
                   _spec((BLOCK, W), jnp.int32, one_chip),
                   _spec((M,), jnp.int32, one_chip))
    assert _kernels(hlo) == 1


def test_create_index_compiles_for_v5e_wide_records(one_chip):
    """Records of 128 words: the records block outgrows the compiler's
    default scoped VMEM, so the kernel asks for what its blocks need."""
    hlo = _compile(functools.partial(ops.create_index, interpret=False),
                   _spec((BLOCK // 4, 128), jnp.int32, one_chip),
                   _spec((256,), jnp.int32, one_chip))
    assert _kernels(hlo) == 1


def test_create_index_runs_in_jit_cam_match(one_chip):
    """Index creation dispatches one jit named ``cam_match`` that holds the
    record relayout and the kernel, and it lowers to the module
    ``jit_cam_match`` — the module the benchmark's ``create_roofline``
    times."""
    args = (_spec((BLOCK, W), jnp.int32, one_chip),
            _spec((M,), jnp.int32, one_chip))
    eqns = jax.make_jaxpr(functools.partial(ops.create_index,
                                            interpret=False))(*args).eqns
    jits = [e for e in eqns if "jaxpr" in e.params]
    kernels = [e for e in jits
               if any(q.primitive.name == "pallas_call"
                      for q in e.params["jaxpr"].jaxpr.eqns)]
    assert [e.params["name"] for e in kernels] == ["cam_match"]
    inner = {q.primitive.name for q in kernels[0].params["jaxpr"].jaxpr.eqns}
    assert "transpose" in inner
    block_w, _, block_m, _ = ops._create_blocks(BLOCK, M)
    lowered = cm.cam_match.lower(*args, block_w=block_w, block_m=block_m,
                                 interpret=False)
    assert re.search(r"^module @jit_cam_match\b", lowered.as_text(),
                     re.MULTILINE)


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.uint16])
def test_narrowed_records_widen_inside_jit_cam_match(one_chip, dtype):
    """Records that cross to the chip narrowed (``BitmapDB.append_encoded``
    at 256 keys sends uint8) reach ``jit_cam_match`` as they are, so the
    widening to int32 is part of its relayout and no dispatch of its own,
    and the pipeline compiles at the benchmark's block."""
    fn = functools.partial(ops.create_index, interpret=False)
    args = (_spec((BLOCK, W), dtype, one_chip),
            _spec((256,), jnp.int32, one_chip))
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    records = jaxpr.invars[0]
    uses = [e for e in jaxpr.eqns if records in e.invars]
    assert [e.params.get("name") for e in uses] == ["cam_match"]
    assert _kernels(_compile(fn, *args)) == 1


def test_pallas_bucket_executor_compiles_for_v5e(one_chip):
    """The ``pallas`` backend's serving path: the fused query kernel
    vmapped over every pass of a bucket."""
    hlo = _compile(jax.vmap(functools.partial(ops.query, interpret=False)),
                   _spec((Q * G * P, L, NW), jnp.uint32, one_chip),
                   _spec((Q * G * P, L), jnp.int32, one_chip))
    assert _kernels(hlo) == 1


def test_bulk_bucket_executor_compiles_for_v5e(one_chip):
    """The ``bulk`` backend's serving path: the whole-bucket sweep plus
    the tail mask and popcount."""
    hlo = _compile(functools.partial(bulk.run_program_pallas,
                                     interpret=False),
                   _spec((M + 1, NW), jnp.uint32, one_chip),
                   _spec((), jnp.int32, one_chip),
                   _spec((Q, G, P, L), jnp.int32, one_chip),
                   _spec((Q, G, P, L), jnp.int32, one_chip),
                   _spec((Q, G, P), jnp.uint32, one_chip))
    assert _kernels(hlo) == 1
