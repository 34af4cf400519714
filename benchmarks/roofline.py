"""Roofline analysis: LM dry-run artifacts + the bitmap-path calibration.

Two independent sections share this CLI:

**LM roofline** (``python benchmarks/roofline.py [out_dir]``) reads
results/dryrun/*.json (written by repro.launch.dryrun) and derives,
per (arch x shape x mesh):

  compute term    = HLO_FLOPs_corrected / (chips x 197 TFLOP/s)
  memory term     = HLO_bytes_corrected / (chips x 819 GB/s)
  collective term = collective_bytes_corrected / (chips x 50 GB/s link)

Corrections (documented, since XLA cost_analysis counts loop bodies once):
  1. Layer scan: corrected = L0 + L x (full - L0), where L0 is the
     num_layers=0 compile of the same cell.
  2. Attention chunk loops: the flash fwd (lax.map over nq q-chunks x scan
     over nk kv-chunks) and its custom-VJP bwd are counted once per layer;
     the missing (nq*nk - 1)/(nq*nk) fraction is added analytically.

All HLO quantities are PER-DEVICE (the partitioned module); MODEL_FLOPS is
global and the ratio uses HLO x num_devices.

**Bitmap roofline** (``python benchmarks/roofline.py bitmap [path]``)
measures the packed-bitmap query path on THIS host — STREAM-class copy
bandwidth plus per-backend sustained words/sec and dispatch overhead — and
persists the calibration JSON the cost model (`repro.engine.costmodel`)
loads to make ``auto`` a measured choice.  :func:`bitmap_roofline` is the
importable entry point.

Nothing LM-related imports at module load: the heavy ``repro.configs`` /
model imports happen inside the LM functions, so importing this module (or
running the bitmap section) never drags in the LM stack.
"""
from __future__ import annotations

import glob
import json
import os
import sys

PEAK_FLOPS = 197e12          # bf16 per chip (TPU v5e)
HBM_BW = 819e9               # B/s per chip
LINK_BW = 50e9               # B/s per ICI link


def _ensure_src() -> None:
    """Make ``repro`` importable when run from the repo root as a script
    (no-op when the package is already on the path)."""
    try:
        import repro  # noqa: F401
    except ImportError:
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "src"))

Q_CHUNK, KV_CHUNK = 512, 1024


def _attn_blocks(S: int, qc: int, kc: int, block_skip: bool) -> int:
    nq, nk = -(-S // qc), -(-S // kc)
    if not block_skip:
        return nq * nk
    return sum(((i + 1) * qc + kc - 1) // kc for i in range(nq))


def _attn_correction(cfg, shape, num_devices: int,
                     block_skip: bool = False) -> tuple[float, float]:
    """(flops, bytes) missing per device due to attention chunk loops.

    The executed attention work is ``blocks`` chunk pairs of (qc x kc) each
    (the dense grid, or the causal-triangular subset under block_skip); the
    HLO counts one pair per loop, so the missing fraction is 1 - 1/blocks.
    """
    if cfg.block == "ssm" or shape.kind == "decode":
        return 0.0, 0.0
    S = shape.seq_len
    B = shape.global_batch
    qc = Q_CHUNK
    kc = qc if block_skip else KV_CHUNK
    blocks = _attn_blocks(S, qc, kc, block_skip)
    if blocks <= 1:
        return 0.0, 0.0
    frac = 1.0 - 1.0 / blocks
    mm = 4.0 * B * qc * kc * blocks * cfg.num_heads * cfg.head_dim
    if shape.kind == "train":
        per_layer = mm * (1 + 1) + mm * 2.5     # fwd + remat refwd + bwd(5mm)
    else:
        per_layer = mm
    flops = per_layer * cfg.num_layers * frac / num_devices
    blk_bytes = (qc * cfg.num_heads + 2 * kc * cfg.num_kv_heads
                 ) * cfg.head_dim * 2.0
    passes = 3 if shape.kind == "train" else 1
    bytes_ = (blocks * blk_bytes * B * cfg.num_layers * passes * frac
              / num_devices)
    if cfg.enc_dec:
        Se = cfg.enc_frames
        blocks_e = _attn_blocks(Se, Q_CHUNK, KV_CHUNK, False)
        if blocks_e > 1:
            mm_e = 4.0 * B * Se * Se * cfg.num_heads * cfg.head_dim
            fr_e = 1.0 - 1.0 / blocks_e
            mult = 4.5 if shape.kind == "train" else 1.0
            flops += mm_e * mult * cfg.enc_layers * fr_e / num_devices
    return flops, bytes_


def _model_flops(cfg, shape) -> float:
    """Assignment definition: 6*N*D train (N_active for MoE); serving uses
    2*N*tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # one token per sequence


def _corrected(cell: dict, key: str, L: int) -> float | None:
    full = cell.get(key)
    l0 = (cell.get("l0") or {}).get(key)
    if full is None:
        return None
    if l0 is None:
        return full
    return l0 + L * (full - l0)


def _corrected_coll(cell: dict, L: int) -> float | None:
    full = (cell.get("collective_bytes") or {}).get("total")
    l0 = ((cell.get("l0") or {}).get("collective_bytes") or {}).get("total")
    if full is None:
        return None
    if l0 is None:
        return full
    return l0 + L * (full - l0)


def _lm_imports():
    """The LM-stack imports, deferred to first use (see module docstring)."""
    _ensure_src()
    from repro.configs import get_config
    from repro.launch.shapes import SHAPES
    from repro.models.model import global_flags  # noqa: F401  (flag defs)
    return get_config, SHAPES


def analyze(cell: dict) -> dict | None:
    if cell.get("status") != "ok":
        return None
    get_config, SHAPES = _lm_imports()
    cfg = get_config(cell["arch"])
    shape = SHAPES[cell["shape"]]
    nd = cell.get("num_devices", 256)
    L = cfg.num_layers
    flops = _corrected(cell, "flops", L)
    bytes_ = _corrected(cell, "bytes_accessed", L)
    coll = _corrected_coll(cell, L)
    block_skip = "block_skip" in (cell.get("variant") or "")
    af, ab = _attn_correction(cfg, shape, nd, block_skip=block_skip)
    # The L0 subtraction can slightly overshoot when the L0 graph keeps
    # fusion opportunities the full graph loses — clamp at zero.
    flops = max((flops or 0.0), 0.0) + af
    bytes_ = max((bytes_ or 0.0), 0.0) + ab

    t_c = flops / PEAK_FLOPS
    t_m = bytes_ / HBM_BW
    t_n = (coll or 0.0) / LINK_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
              key=lambda kv: kv[1])[0]
    mf = _model_flops(cfg, shape)
    ratio = mf / (flops * nd) if flops else float("nan")
    frac = {"compute": t_c, "memory": t_m, "collective": t_n}
    total = max(t_c, t_m, t_n)
    roofline_frac = t_c / total if total else 0.0
    return {
        "arch": cell["arch"], "shape": cell["shape"], "mesh": cell["mesh"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "dominant": dom, "model_flops": mf,
        "hlo_flops_global": flops * nd, "useful_ratio": ratio,
        "roofline_fraction": roofline_frac,
        "mem_gb": ((cell.get("memory") or {}).get("temp_size_in_bytes") or 0)
        / 1e9,
    }


def suggestion(r: dict) -> str:
    if r["dominant"] == "collective":
        return ("reduce resharding: gather weights once per layer "
                "(FSDP prefetch) or switch attention TP to sequence-parallel")
    if r["dominant"] == "memory":
        return ("raise arithmetic intensity: larger microbatch per device, "
                "fuse norms/rope into matmuls, bf16 moments")
    return ("compute-bound (good): shave redundant FLOPs — causal block "
            "skipping in flash, drop remat on cheap layers")


def markdown(rows: list[dict]) -> str:
    """§Roofline markdown table (single-pod cells only, per assignment)."""
    lines = [
        "| arch | shape | compute s | memory s | collective s | dominant | "
        "MODEL/HLO | roofline frac | next move |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r.get("mesh") != "16x16":
            continue
        if "skipped" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                         f"| — | skipped: {r['skipped'][:48]} |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{100*r['roofline_fraction']:.1f}% | {suggestion(r)[:58]} |")
    return "\n".join(lines)


def main(out_dir: str = "results/dryrun") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        r = analyze(cell)
        if r:
            rows.append(r)
        elif cell.get("status") == "skipped":
            rows.append({"arch": cell["arch"], "shape": cell["shape"],
                         "mesh": cell["mesh"], "skipped": cell["reason"]})
    hdr = (f"{'arch':24s} {'shape':12s} {'mesh':8s} {'compute_s':>10s} "
           f"{'memory_s':>10s} {'collect_s':>10s} {'dom':>10s} "
           f"{'useful':>7s} {'roofl%':>7s}")
    print(hdr)
    for r in rows:
        if "skipped" in r:
            print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} "
                  f"SKIPPED: {r['skipped'][:60]}")
            continue
        print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} "
              f"{r['compute_s']:10.4f} {r['memory_s']:10.4f} "
              f"{r['collective_s']:10.4f} {r['dominant']:>10s} "
              f"{r['useful_ratio']:7.2f} {100*r['roofline_fraction']:6.1f}%")
    return rows


# --------------------------------------------------------------- bitmap
def bitmap_roofline(path: str | None = None, *, num_records: int = 1 << 20,
                    num_keys: int = 256, save: bool = True) -> dict:
    """Measure the bitmap query path's roofline on this host and (by
    default) persist the calibration JSON the cost model loads.

    Returns a plain dict: the measured copy bandwidth, per-backend
    words/sec + dispatch overhead + bandwidth utilization (streamed bytes
    over copy bytes/sec), and where the calibration was written.
    Importable — ``repro.engine.costmodel`` does the measuring; this
    wrapper only formats and persists.
    """
    _ensure_src()
    from repro.engine import costmodel

    cal = costmodel.measure_calibration(num_records=num_records,
                                        num_keys=num_keys)
    out = {
        "platform": cal.platform,
        "copy_bytes_per_sec": cal.copy_bytes_per_sec,
        "backends": {
            n: {
                "words_per_sec": p.words_per_sec,
                "dispatch_overhead_s": p.dispatch_overhead_s,
                "bandwidth_utilization":
                    p.words_per_sec * 4.0 / cal.copy_bytes_per_sec,
            } for n, p in cal.profiles
        },
    }
    if save:
        where = costmodel.save_calibration(cal, path)
        costmodel.set_calibration(cal)
        out["calibration_path"] = where
    return out


def bitmap_main(path: str | None = None) -> dict:
    r = bitmap_roofline(path)
    print(f"platform: {r['platform']}")
    print(f"copy bandwidth: {r['copy_bytes_per_sec'] / 1e9:.2f} GB/s")
    print(f"{'backend':10s} {'words/s':>12s} {'overhead us':>12s} "
          f"{'bw util':>8s}")
    for n, p in sorted(r["backends"].items()):
        print(f"{n:10s} {p['words_per_sec']:12.3e} "
              f"{p['dispatch_overhead_s'] * 1e6:12.1f} "
              f"{100 * p['bandwidth_utilization']:7.1f}%")
    if "calibration_path" in r:
        print(f"calibration written to {r['calibration_path']}")
    return r


if __name__ == "__main__":
    _ensure_src()
    from repro import jaxcache
    jaxcache.enable()
    if len(sys.argv) > 1 and sys.argv[1] == "bitmap":
        bitmap_main(sys.argv[2] if len(sys.argv) > 2 else None)
    else:
        main(sys.argv[1] if len(sys.argv) > 1 else "results/dryrun")
