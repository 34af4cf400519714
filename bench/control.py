"""Run a cell's control: its plain reference with one stated guarantee
broken, put in the program's place, compared as a run compares.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>
        [--sessions <n>]

``ssb-sf1``'s control answers ranges as binned supersets (exact predicates
broken); ``bic-paper``'s indexes all but the last word of each record.  For
each seed it prints the numbers compared and their limits, which must come
out not correct.  The program is not run: the control answers every query
a window of ``--seconds`` would compare (``open_loop``), each stream's
first ``control_per_stream`` queries, a key of the mix (``closed_loop``:
what a window on the chip got through), or every sampled block of
``--sessions`` whole sessions (``load``).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control(cell_name: str, seed: int, seconds: float, sessions: int,
            sizes=None, mix_overrides=None, root=None) -> dict:
    from bench import harness, traffic

    _, drv = harness.make_generator(cell_name, seed,
                                    root=root or harness.ROOT, sizes=sizes,
                                    mix_overrides=mix_overrides)
    drv.make_data()
    if isinstance(drv, traffic.ClosedLoop):
        drv.plan_control()
    elif isinstance(drv, traffic.OpenLoop):
        drv.plan_window(seconds)
    else:
        drv.plan_control(sessions)
    checks = drv.check(control=True)
    return {"correct": all(c.ok for c in checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sessions", type=int, default=3)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = control(args.workload, seed, args.seconds, args.sessions)
        out.update(workload=args.workload, seed=seed,
                   seconds=round(time.perf_counter() - t0, 3))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
