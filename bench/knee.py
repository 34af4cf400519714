"""Sweep an open-loop cell's offered rate once, in one process, to find
the knee: the highest rate the system sustains.

    python bench/knee.py --workload ssb-sf1.q12-q13 --seed <n> \\
        --rates 400,800,1600 --seconds 8

Sets the cell up once, then runs one window per rate and prints, per rate,
a JSON line: offered and completed queries per second, p50/p99 latency
from the due time, and whether the backlog grew (the median latency of
the window's last fifth against its first fifth).  Needs the chip.
"""
import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness, traffic
    from repro import jaxcache

    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    jaxcache.enable()
    _, drv = harness.make_generator(args.workload, args.seed)
    if not isinstance(drv, traffic.OpenLoop):
        print("knee: needs an open-loop cell", file=sys.stderr)
        return 2
    mix = drv.mix
    drv.setup()
    print(json.dumps({"setup": drv.phases,
                      "setup_s": time.perf_counter() - T_PROCESS}),
          flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix["rate_per_s"] = rate
        drv.measure(args.seconds)
        s = drv.served
        due = s.t_start + drv.window.due
        in_window = np.count_nonzero(s.t_done <= s.t_start + args.seconds)
        lat = drv.latency_ms
        fifth = max(1, len(lat) // 5)
        first = float(np.median(lat[:fifth]))
        last = float(np.median(lat[-fifth:]))
        print(json.dumps({
            "rate": rate, "offered_per_s": len(due) / args.seconds,
            "completed_per_s": in_window / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_fifth_p50_ms": first, "last_fifth_p50_ms": last,
            "backlog_grew": bool(last > 2 * first + 50),
            "failed": int(s.failed.sum()),
            "gen_lag_p99_ms": float(np.nanpercentile(drv.lag_ms, 99))}),
            flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
