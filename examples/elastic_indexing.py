"""Elastic multi-core BIC (paper Fig. 4 + §III-E): index a workload across
Z cores, activating only as many as the load needs; idle cores sit in
standby under CG / CG+RBB, with energy accounted by the calibrated silicon
model.  Also demonstrates straggler-aware (LPT) dispatch.

Run:  PYTHONPATH=src python examples/elastic_indexing.py
"""
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.elastic import (PowerState, lpt_schedule,  # noqa: E402
                                static_schedule)
from repro.engine.runtime import (MulticoreRuntime,  # noqa: E402
                                  StreamingIndexer)


def main():
    rng = np.random.default_rng(0)

    # --- fused runtime: sharded indexing + elastic energy in one place
    mesh = jax.make_mesh((len(jax.devices()),), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    keys = jnp.asarray(rng.integers(0, 256, (8,), dtype=np.int32))
    # diurnal workload: peak hours, off-peak, idle nights (batches per tick)
    workload = [8] * 6 + [4] * 6 + [0] * 12
    tick = 3600.0 / 24
    ticks = [None if wl == 0 else jnp.asarray(
        rng.integers(0, 256, (wl, 16, 32), dtype=np.int32))
        for wl in workload]
    for name, state in [("CG only", PowerState(use_rbb=False)),
                        ("CG+RBB", PowerState(use_rbb=True))]:
        rt = MulticoreRuntime(mesh, state=state)
        outs, rep = rt.index_stream(ticks, keys, tick_seconds=tick)
        built = sum(o.shape[0] for o in outs)
        print(f"{name:8s}: indexed {built} batches  "
              f"active={rep.active_joules*1e3:9.4f} mJ  "
              f"standby={rep.standby_joules*1e3:9.6f} mJ  "
              f"(standby power {rt.scheduler.p_standby*1e9:.2f} nW/core)")

    # --- streaming ingest: grow one index block-by-block, no rebuild
    si = StreamingIndexer(keys)
    for nblk in (100, 28, 60):
        si.append(jnp.asarray(rng.integers(0, 256, (nblk, 32),
                                           dtype=np.int32)))
    idx = si.index
    print(f"streaming ingest: {idx.num_records} records appended in 3 "
          f"blocks -> packed index {idx.packed.shape} (no full rebuild)")

    # --- straggler mitigation: one slow core (0.25x)
    costs = [1.0] * 64
    speeds = [1.0] * 7 + [0.25]
    mk_static = static_schedule(costs, speeds)
    mk_lpt, _ = lpt_schedule(costs, speeds)
    print(f"straggler: static round-robin makespan={mk_static:.1f}, "
          f"LPT work-stealing={mk_lpt:.1f} "
          f"({mk_static/mk_lpt:.1f}x better)")


if __name__ == "__main__":
    from repro import jaxcache
    jaxcache.enable()
    main()
