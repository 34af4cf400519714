"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps on
the bitmap-indexed data pipeline, with checkpoint/restart fault tolerance.

The data selection ("domain 3, high quality, not flagged") runs as bitmap
queries over BIC-built indexes — the paper's technique in the data plane.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.data.pipeline import BitmapIndexedDataset, DataConfig  # noqa: E402
from repro.engine.planner import key  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.optim.adamw import OptimConfig  # noqa: E402
from repro.train.loop import LoopConfig, train_loop  # noqa: E402
from repro.train.step import TrainConfig  # noqa: E402

# ~100M params: 12L x 768d, GQA 12/4, 32k vocab (qwen2-family reduced)
CFG = ModelConfig(
    name="lm-100m", family="dense", source="examples",
    num_layers=12, d_model=768, num_heads=12, num_kv_heads=4, head_dim=64,
    d_ff=3072, vocab_size=32000, rope="rope", tie_embeddings=True,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    print(f"model: {CFG.param_count()/1e6:.0f}M params")
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=args.seq,
                      docs_per_shard=512, num_shards=4, num_attributes=32)
    ds = BitmapIndexedDataset(dcfg)
    # bitmap-query data selection: domain==3 AND quality==18, NOT flag 25
    sel = dict(where=key(3) & key(18) & ~key(25))
    n_sel = sum(len(ds.select(s, **sel)) for s in range(dcfg.num_shards))
    print(f"bitmap query selected {n_sel} / "
          f"{dcfg.num_shards * dcfg.docs_per_shard} documents")

    def batches(start_step: int):
        return ds.batches(args.batch, seed=0, start_step=start_step, **sel)

    out = train_loop(
        CFG,
        TrainConfig(OptimConfig(peak_lr=3e-4, warmup_steps=20,
                                decay_steps=args.steps)),
        LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                   ckpt_every=100, log_every=10),
        batches)
    print(f"done: final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    from repro import jaxcache
    jaxcache.enable()
    main()
