"""Fused, vocab-parallel cross-entropy (the "never materialize the logits"
loss).

Why: with 152k-262k vocabs, (B, S, V) logits in f32 are multi-GB per device
and their gradient doubles it; the tied-embedding gradient additionally
all-reduces a replicated (d, V) f32 buffer per microbatch.  This module
computes the loss in sequence chunks inside a shard_map:

  * logits exist only as (B_l, chunk, V_l) blocks in VMEM-sized pieces;
  * logsumexp / gold-logit reductions psum over the ``model`` (vocab) axis;
  * dx is reconstructed chunk-by-chunk in the custom backward;
  * the head gradient accumulates locally over chunks and leaves the device
    ONCE per step via reduce-scatter onto its FSDP shard (not AR + slice).

Falls back to a single-device path when no mesh is active (CPU smoke tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


def _ce_core(x, head, labels, valid_vocab: int, chunk: int,
             tp_axis: str | None, dp_axes: tuple[str, ...]):
    """Local (per-shard) fused CE with optional collective reductions.
    x (B, S, d); head (d, V_l); labels (B, S) (-1 = masked).
    Returns (nll_sum, token_count, lse (B, S)) — all pre-dp-reduction."""
    B, S, d = x.shape
    V_l = head.shape[1]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    v_off = (jax.lax.axis_index(tp_axis) * V_l) if tp_axis else 0
    v_ids = v_off + jnp.arange(V_l)
    v_valid = (v_ids < valid_vocab)

    def one_chunk(c):
        x_c = jax.lax.dynamic_slice_in_dim(x, c * chunk, chunk, axis=1)
        l_c = jax.lax.dynamic_slice_in_dim(labels, c * chunk, chunk, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", x_c, head,
                            preferred_element_type=jnp.float32)
        logits = jnp.where(v_valid[None, None], logits, -jnp.inf)
        m = logits.max(axis=-1)
        if tp_axis:
            m = jax.lax.pmax(m, tp_axis)
        z = jnp.exp(logits - m[..., None]).sum(axis=-1)
        if tp_axis:
            z = jax.lax.psum(z, tp_axis)
        lse = m + jnp.log(z)
        l_loc = l_c - v_off
        in_shard = (l_loc >= 0) & (l_loc < V_l)
        gold_l = jnp.take_along_axis(
            logits, jnp.clip(l_loc, 0, V_l - 1)[..., None], axis=-1)[..., 0]
        gold = jnp.where(in_shard, gold_l, 0.0)
        if tp_axis:
            gold = jax.lax.psum(gold, tp_axis)
        mask = (l_c >= 0)
        nll = jnp.where(mask, lse - gold, 0.0)
        return nll.sum(), mask.sum(), lse

    sums, counts, lses = [], [], []
    for c in range(nc):           # static chunk count; bodies are small
        s_, n_, lse_ = one_chunk(c)
        sums.append(s_)
        counts.append(n_)
        lses.append(lse_)
    lse = jnp.concatenate(lses, axis=1)[:, :S]
    return sum(sums), sum(counts), lse


def _make_local_loss(valid_vocab: int, chunk: int, tp_axis, dp_axes):

    @jax.custom_vjp
    def local_loss(x, head, labels):
        nll, cnt, _ = _ce_core(x, head, labels, valid_vocab, chunk,
                               tp_axis, dp_axes)
        return _finalize(nll, cnt)

    def _finalize(nll, cnt):
        nll = nll.astype(jnp.float32)
        cnt = cnt.astype(jnp.float32)
        for ax in dp_axes:
            nll = jax.lax.psum(nll, ax)
            cnt = jax.lax.psum(cnt, ax)
        return nll / jnp.maximum(cnt, 1.0), cnt

    def fwd(x, head, labels):
        nll, cnt, lse = _ce_core(x, head, labels, valid_vocab, chunk,
                                 tp_axis, dp_axes)
        loss, cnt_g = _finalize(nll, cnt)
        return (loss, cnt_g), (x, head, labels, lse, cnt_g)

    def bwd(res, g):
        x, head, labels, lse, cnt_g = res
        gl, _ = g
        B, S, d = x.shape
        V_l = head.shape[1]
        nc = -(-S // chunk)
        pad = nc * chunk - S
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
            lse = jnp.pad(lse, ((0, 0), (0, pad)))
        v_off = (jax.lax.axis_index(tp_axis) * V_l) if tp_axis else 0
        v_ids = v_off + jnp.arange(V_l)
        v_valid = (v_ids < valid_vocab)
        w = gl / jnp.maximum(cnt_g, 1.0)

        dx_chunks = []
        dhead = jnp.zeros(head.shape, jnp.float32)
        for c in range(nc):
            x_c = jax.lax.dynamic_slice_in_dim(x, c * chunk, chunk, axis=1)
            l_c = jax.lax.dynamic_slice_in_dim(labels, c * chunk, chunk, axis=1)
            lse_c = jax.lax.dynamic_slice_in_dim(lse, c * chunk, chunk, axis=1)
            logits = jnp.einsum("bsd,dv->bsv", x_c, head,
                                preferred_element_type=jnp.float32)
            logits = jnp.where(v_valid[None, None], logits, -jnp.inf)
            p = jnp.exp(logits - lse_c[..., None])
            l_loc = l_c - v_off
            onehot = (l_loc[..., None] == jnp.arange(V_l)[None, None])
            mask = (l_c >= 0).astype(jnp.float32)
            dlogits = (p - onehot.astype(jnp.float32)) * (w * mask)[..., None]
            dlogits = jnp.where(v_valid[None, None], dlogits, 0.0)
            dx_c = jnp.einsum("bsv,dv->bsd", dlogits,
                              head.astype(jnp.float32))
            if tp_axis:
                dx_c = jax.lax.psum(dx_c, tp_axis)
            dx_chunks.append(dx_c.astype(x.dtype))
            dhead = dhead + jnp.einsum("bsd,bsv->dv",
                                       x_c.astype(jnp.float32), dlogits)
        dx = jnp.concatenate(dx_chunks, axis=1)[:, :S]
        # head grad leaves the device once: reduce-scatter onto the FSDP
        # shard of d (dp_axes) would change the local shape, so psum here
        # and let the partitioner keep it sharded via the grad constraint.
        for ax in dp_axes:
            dhead = jax.lax.psum(dhead, ax)
        return dx, dhead.astype(head.dtype), None

    local_loss.defvjp(fwd, bwd)
    return local_loss


def fused_ce_loss(x: Array, head: Array, labels: Array, *,
                  valid_vocab: int, chunk: int = 1024
                  ) -> tuple[Array, Array]:
    """Mean next-token NLL over labels >= 0.  x (B,S,d), head (d, Vp).
    Returns (loss, token_count)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        fn = _make_local_loss(valid_vocab, chunk, None, ())
        return fn(x, head, labels)

    names = mesh.axis_names
    sizes = dict(mesh.shape)
    dp = tuple(a for a in ("pod", "data") if a in names and sizes[a] > 1)
    tp = "model" if "model" in names and sizes["model"] > 1 else None
    B, S, d = x.shape
    Vp = head.shape[1]
    dp_div = 1
    for a in dp:
        dp_div *= sizes[a]
    if B % max(dp_div, 1) or (tp and Vp % sizes["model"]):
        fn = _make_local_loss(valid_vocab, chunk, None, ())
        return fn(x, head, labels)

    fn = _make_local_loss(valid_vocab, chunk, tp, dp)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, tp), P(dp_spec, None)),
        out_specs=(P(), P()),
        check_vma=False)
    return mapped(x, head, labels)
