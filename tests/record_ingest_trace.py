"""Record a profiler trace of a few traced ``append_encoded`` calls on the
chip, the input of ``tests/test_ingest_trace.py``.

    python tests/record_ingest_trace.py --out tests/data/ingest.xplane.pb

Appends blocks of the benchmark's index-creation size (2^18 records of 32
8-bit words, 256 keys) into a session with a ``repro.obs`` tracer
installed while the profiler runs, after a warm-up session that compiles
every width the traced one uses.  Writes the ``.xplane.pb`` and, beside
it as ``<out>.json``, the ``perf_counter`` reading taken inside the
``bench.sync`` annotation (``sync_pc``) and the recorded spans.  Needs
the chip.
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
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--block", type=int, default=1 << 18)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import repro
    from bench import trace_reduce
    from repro import jaxcache
    from repro.obs import trace as obs_trace

    if jax.devices()[0].platform != "tpu":
        print("record_ingest_trace: needs a TPU", file=sys.stderr)
        return 2
    jaxcache.enable()
    block, n = args.block, args.blocks
    recs = np.random.default_rng(0).integers(
        0, 256, (n * block, 32), dtype=np.uint8).astype(np.int32)

    def session():
        return repro.BitmapDB(num_keys=256, backend="auto",
                              capacity_words=n * block // 32 + 1024)

    def fill(db):
        for b in range(n):
            db.append_encoded(recs[b * block:(b + 1) * block])
        jax.block_until_ready(db.indexer.view()[0])

    fill(session())                     # compiles every width, untraced
    db = session()
    tracer = obs_trace.install(obs_trace.Tracer())
    log_dir = tempfile.mkdtemp(prefix="ingest-record-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
            sync = time.perf_counter()
        fill(db)
        jax.profiler.stop_trace()
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copyfile(trace_reduce.find_xplane(log_dir), args.out)
    finally:
        obs_trace.uninstall(tracer)
        shutil.rmtree(log_dir, ignore_errors=True)
    spans = [s.to_dict() for s in tracer.spans()]
    with open(args.out + ".json", "w") as f:
        json.dump({"sync_pc": sync, "block": block, "blocks": n,
                   "spans": spans}, f, indent=0)
    red = trace_reduce.reduce(args.out, sync)
    print(json.dumps({"busy_s": red.busy_s, "window_s": red.window_s,
                      "modules": sorted({m[0] for m in red.modules}),
                      "gaps": trace_reduce.label_gaps(
                          red, tracer.spans(), top=20)}))
    for s in spans:
        print(s["name"], round(s["dur_ms"], 3))
    return 0


if __name__ == "__main__":
    sys.exit(main())
