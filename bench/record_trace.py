"""Record a small profiler trace of index creation on the chip, the input
of the trace-reduction test (``bench/testdata/``).

    python bench/record_trace.py --out bench/testdata/create_index.xplane.pb

Appends a few blocks of records into a session while the profiler runs,
with the harness's clock annotation, and writes the ``.xplane.pb`` and
the ``perf_counter`` reading taken inside the annotation
(``<out>.json``).  Needs the chip.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--blocks", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import repro
    from bench import trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    block = 1 << 14
    recs = np.random.default_rng(0).integers(
        0, 256, (args.blocks * block, 32), dtype=np.uint8).astype(np.int32)
    db = repro.BitmapDB(num_keys=256, backend="auto",
                        capacity_words=args.blocks * block // 32 + 1024)
    db.append_encoded(recs[:block])              # compile outside the trace
    jax.block_until_ready(db.indexer.view()[0])
    log_dir = tempfile.mkdtemp(prefix="bench-record-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
            sync = time.perf_counter()
        for b in range(1, args.blocks):
            db.append_encoded(recs[b * block:(b + 1) * block])
        jax.block_until_ready(db.indexer.view()[0])
        jax.profiler.stop_trace()
        src = trace_reduce.find_xplane(log_dir)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copyfile(src, args.out)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    red = trace_reduce.reduce(args.out, sync)
    with open(args.out + ".json", "w") as f:
        json.dump({"sync_pc": sync, "busy_s": red.busy_s,
                   "window_s": red.window_s, "devices": red.devices,
                   "modules": sorted({m[0] for m in red.modules}),
                   "top_ops": trace_reduce.top_ops(red)}, f, indent=1)
    data = jax.profiler.ProfileData.from_file(args.out)
    for plane in data.planes:
        print(plane.name, [(ln.name, len(list(ln.events)))
                           for ln in plane.lines][:12])
    print(open(args.out + ".json").read())
    return 0


if __name__ == "__main__":
    sys.exit(main())
