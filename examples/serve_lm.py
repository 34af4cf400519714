"""Serving example: batched prefill + decode with a KV cache on a small LM,
with bitmap-indexed request routing — requests carry attribute tags (user
tier, task type) and a BIC index over the waiting queue lets the scheduler
pull matching batches with one bitwise query (the serving-plane analogue of
the paper's multi-dimensional queries).

The routing queries go through a :class:`repro.serve.BitmapService`: each
scheduling policy submits its selection concurrently, the service
coalesces them into one bucketed dispatch, and between request waves it
duty-cycles into standby — the paper's operating model applied to the
serving control plane.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.bic import BICConfig, BICCore  # noqa: E402
from repro.engine.planner import key  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.model import init_params  # noqa: E402
from repro.serve import BitmapService  # noqa: E402
from repro.serve.step import greedy_generate  # noqa: E402

CFG = ModelConfig(
    name="serve-demo", family="dense", source="examples",
    num_layers=4, d_model=256, num_heads=8, num_kv_heads=4, head_dim=32,
    d_ff=1024, vocab_size=8192, rope="rope", tie_embeddings=True,
)


def main():
    rng = np.random.default_rng(0)
    params = init_params(CFG, jax.random.PRNGKey(0))

    # --- request queue with attribute tags, indexed by a BIC core
    n_req, n_tags = 64, 16
    tags = rng.integers(0, n_tags, size=(n_req, 4)).astype(np.int32)
    bic = BICCore(BICConfig(num_keys=n_tags, num_records=n_req,
                            words_per_record=4))
    index = bic.create(jnp.asarray(tags), jnp.arange(n_tags, dtype=jnp.int32))
    # scheduling policies submit concurrently; the service coalesces them
    # into one bucketed dispatch and idles in standby between waves
    svc = BitmapService.open(index, max_delay_ms=2.0, idle_after_ms=25.0)
    policies = {
        # premium (tag 2) non-batch-exempt (not tag 7) requests first
        "premium": key(2) & ~key(7),
        "interactive": key(1) | key(3),
        "batch_tier": key(7) & ~key(2),
    }
    futs = {name: svc.submit(q) for name, q in policies.items()}
    svc.drain()
    ready = [int(i) for i in futs["premium"].ids]
    print(f"scheduler: {futs['premium'].count} premium / "
          f"{futs['interactive'].count} interactive / "
          f"{futs['batch_tier'].count} batch requests selected in "
          f"{svc.metrics().batches} coalesced dispatch(es): {ready[:8]}...")

    # --- batched prefill + decode on the selected batch
    batch = ready[:8] if len(ready) >= 8 else list(range(8))
    prompts = jnp.asarray(
        rng.integers(0, CFG.vocab_size, size=(len(batch), 32)))
    t0 = time.time()
    out = greedy_generate(params, CFG, prompts, steps=16)
    dt = time.time() - t0
    toks = out.size
    print(f"generated {toks} tokens for {len(batch)} requests "
          f"in {dt:.2f}s ({toks/dt:.0f} tok/s on CPU)")
    print("sample continuation:", np.asarray(out[0])[:8].tolist())

    # --- duty cycle: the routing service idled (or clock-gated) while the
    # LM generated; its meter shows the active/standby split
    m = svc.metrics()
    print(f"routing service: state={m.state} served={m.served} "
          f"active={m.active_joules:.2e}J standby={m.standby_joules:.2e}J")
    svc.close()


if __name__ == "__main__":
    from repro import jaxcache
    jaxcache.enable()
    main()
