"""Compile the SSB cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/compile_widths.py

Compiles, at the widths ``ssb-sf1`` holds (1,795 key rows of
187,500 words; 2^18-record ingest blocks of 14 words): the whole index
creation (``cam_match`` and ``bit_transpose``), and the bucket executor of
each of the 7 bucket shapes the 13 SSB templates lower to, on each query
backend, at the widest wave (256 queries).  Prints each program's ``memory_analysis()`` and compile
time.  Nothing runs: this says what the chip's compiler accepts and how
much memory a program asks for, never a time on the chip.
"""
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, traffic
    from repro.db import Column, Schema
    from repro.engine import backends, batch, bulk
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    cfg = harness.load_config("ssb-sf1")
    sizes, ref = cfg.sizes, cfg.module
    schema = Schema([Column.categorical(n, v) for n, v in ref.columns(sizes)])
    m = schema.num_keys
    nw = -(-sizes["lineorder_rows"] // 32)
    block = sizes["block_records"]

    def report(what, fn, *specs):
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(fn).lower(*specs).compile()
        except Exception as e:  # noqa: BLE001 — reported, next program
            print(json.dumps({"program": what, "error": str(e)[:400]}),
                  flush=True)
            return
        ma = compiled.memory_analysis()
        print(json.dumps({
            "program": what, "compile_s": round(time.perf_counter() - t0, 3),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes}),
            flush=True)

    report(f"create_index records=({block},{len(schema.columns)}) keys={m}",
           functools.partial(ops.create_index, interpret=False),
           spec((block, len(schema.columns)), jnp.int32),
           spec((m,), jnp.int32))

    # the bucket shapes of one query of each template
    from repro.db import expr as expr_mod
    from repro.engine import planner
    r = __import__("bench.reference", fromlist=["rng"]).rng(0, "queries")
    shapes = {}
    for t in ref.TEMPLATES:
        pl = planner.plan(expr_mod.lower(
            traffic.to_expr(ref.draw(sizes, r, t)), schema))
        shapes.setdefault(batch._lowered(pl)[1], t)
    print(json.dumps({"bucket_shapes": {str(k): v
                                        for k, v in shapes.items()}}))
    per_pass = {
        "pallas": backends.Backend(
            "pallas", None, functools.partial(ops.query, interpret=False)),
        "ref": backends.get_backend("ref"),
    }
    q = 256
    for (g, p, l), t in sorted(shapes.items()):
        args = (spec((m + 1, nw), jnp.uint32), spec((), jnp.int32),
                spec((q, g, p, l), jnp.int32), spec((q, g, p, l), jnp.int32),
                spec((q, g, p), jnp.uint32))
        report(f"bulk {t} shape=({g},{p},{l}) q={q}",
               functools.partial(bulk.run_program_pallas, interpret=False),
               *args)
        for name, be in per_pass.items():
            report(f"{name} {t} shape=({g},{p},{l}) q={q}",
                   batch._bucket_body(be, p, g), *args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
