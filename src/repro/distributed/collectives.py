"""Distributed-optimization building blocks.

* compressed_allreduce — int8/bf16 quantized gradient all-reduce with
  error feedback (residual carried across steps).  At 1000+-node scale
  gradient all-reduce bytes dominate the interconnect; int8 cuts them 4x
  vs fp32 at the cost of quantization noise that error feedback absorbs.
* ring_allgather_matmul — shard_map ppermute ring that overlaps the
  all-gather of a weight shard with the partial matmul (compute/comm
  overlap, the classic latency-hiding schedule).
* flash_decode — sequence-sharded decode attention: each model shard
  attends over its slice of the KV cache and partial softmaxes combine
  with log-sum-exp weights (psum), avoiding the all-gather of 32k-token
  caches.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


# --------------------------------------------------------------------------
# Gradient compression with error feedback
# --------------------------------------------------------------------------
def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_grads(grads, residuals, axis_name: str,
                          mode: str = "int8"):
    """All-reduce `grads` across `axis_name` with compression + error
    feedback.  Call INSIDE shard_map.  Returns (mean grads, residuals)."""
    n = jax.lax.psum(1, axis_name)

    def one(g, r):
        g = g.astype(jnp.float32) + r
        if mode == "bf16":
            sent = g.astype(jnp.bfloat16)
            recon = sent.astype(jnp.float32)
            reduced = jax.lax.psum(sent.astype(jnp.float32), axis_name)
        else:
            # shards must agree on the scale (a per-shard scale cannot
            # dequantize the summed ints): one scalar pmax, then int8.
            scale = jax.lax.pmax(jnp.max(jnp.abs(g)), axis_name) / 127.0 \
                + 1e-12
            q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
            recon = q.astype(jnp.float32) * scale
            # int8 psum: widen to int32 for the reduction, rescale after.
            reduced = jax.lax.psum(q.astype(jnp.int32), axis_name) \
                .astype(jnp.float32) * scale
        return reduced / n, g - recon

    out = jax.tree_util.tree_map(one, grads, residuals)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1)


# --------------------------------------------------------------------------
# Overlapped ring all-gather matmul
# --------------------------------------------------------------------------
def ring_allgather_matmul(mesh: Mesh, axis: str = "model") -> Callable:
    """y = x @ W with W row-sharded over `axis`; the ring permutes W shards
    while multiplying the resident shard — overlap instead of a blocking
    all-gather.  x: (B, K) replicated rows, W: (K, N) sharded on K."""
    n_shards = mesh.shape[axis]

    def local(x, w_shard):
        idx = jax.lax.axis_index(axis)
        k_per = w_shard.shape[0]

        def body(i, carry):
            acc, w_cur, src = carry
            x_slice = jax.lax.dynamic_slice_in_dim(
                x, src * k_per, k_per, axis=1)
            acc = acc + x_slice @ w_cur
            w_nxt = jax.lax.ppermute(
                w_cur, axis,
                [(j, (j + 1) % n_shards) for j in range(n_shards)])
            return acc, w_nxt, (src - 1) % n_shards

        acc0 = jnp.zeros((x.shape[0], w_shard.shape[1]), x.dtype)
        # mark the accumulator as device-varying over the ring axis so the
        # loop carry types line up with the permuted weight shard
        acc0 = jax.lax.pcast(acc0, (axis,), to="varying")
        acc, _, _ = jax.lax.fori_loop(0, n_shards, body,
                                      (acc0, w_shard, idx))
        return acc

    # After a full ring rotation every shard holds the complete sum; the
    # vma checker cannot prove that, hence check_vma=False.
    return shard_map(local, mesh=mesh,
                     in_specs=(P(None, None), P(axis, None)),
                     out_specs=P(None, None), check_vma=False)


# --------------------------------------------------------------------------
# Ring attention: sequence-sharded full attention (prefill / train)
# --------------------------------------------------------------------------
def ring_attention(mesh: Mesh, *, axis: str = "model",
                   dp=("data",), unroll: bool = False) -> Callable:
    """Causal GQA attention with Q, K, V sharded on the SEQUENCE dim over
    `axis`.  KV blocks rotate around the ring (ppermute) while each shard
    accumulates its query block with an online softmax — no shard ever
    holds more than S/n of the sequence, and no head-count divisibility
    is required (the cure for small archs whose 14/12 heads cannot shard
    a 16-way model axis: without this, GSPMD replicates the whole
    attention on every shard).

    q, k, v: (B, S, H|KVH, Dh) with S sharded over `axis`.
    """
    n = mesh.shape[axis]

    def local(q, k, v):
        B, S_loc, H, Dh = q.shape
        KVH = k.shape[2]
        G = H // KVH
        idx = jax.lax.axis_index(axis)
        q_off = idx * S_loc
        qg = q.reshape(B, S_loc, KVH, G, Dh)
        scale = Dh ** -0.5
        qpos = q_off + jnp.arange(S_loc)

        o0 = jax.lax.pcast(jnp.zeros((B, KVH, G, S_loc, Dh), jnp.float32),
                           (axis,), to="varying")
        m0 = jax.lax.pcast(jnp.full((B, KVH, G, S_loc), -1e30, jnp.float32),
                           (axis,), to="varying")
        l0 = jax.lax.pcast(jnp.zeros((B, KVH, G, S_loc), jnp.float32),
                           (axis,), to="varying")

        def step(j, carry):
            o, m, l, kc, vc = carry
            src = (idx - j) % n                  # origin shard of kc block
            kpos = src * S_loc + jnp.arange(S_loc)
            s = jnp.einsum("bqhgd,bshd->bhgqs", qg, kc,
                           preferred_element_type=jnp.float32) * scale
            mask = qpos[:, None] >= kpos[None, :]          # causal
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            o = o * corr[..., None] + pv
            perm = [(r, (r + 1) % n) for r in range(n)]
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return o, m_new, l, kc, vc

        carry = (o0, m0, l0, k, v)
        if unroll:       # cost probes: loop bodies must be in counted HLO
            for j in range(n):
                carry = step(j, carry)
            o, m, l = carry[:3]
        else:
            o, m, l, _, _ = jax.lax.fori_loop(0, n, step, carry)
        out = o / jnp.maximum(l[..., None], 1e-30)         # (B,KVH,G,S,Dh)
        out = jnp.moveaxis(out, 3, 1).reshape(B, S_loc, H, Dh)
        return out.astype(q.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, axis, None, None), P(dp, axis, None, None),
                  P(dp, axis, None, None)),
        out_specs=P(dp, axis, None, None), check_vma=False)


# --------------------------------------------------------------------------
# Flash-decode: sequence-sharded decode attention
# --------------------------------------------------------------------------
def flash_decode(mesh: Mesh, *, axis: str = "model",
                 dp: tuple = ("data",)) -> Callable:
    """One-token GQA attention with the KV cache sharded on the sequence
    dim.  Each shard computes a partial softmax over its S/n slice; the
    partials combine exactly via LSE weights in a single psum — no
    KV all-gather.

    q: (B, H, Dh) replicated over `axis`; k/v: (B, S, KVH, Dh) sharded on
    S; valid_len: scalar count of valid positions (global).
    """

    def local(q, k, v, valid_len):
        B, H, Dh = q.shape
        S_loc, KVH = k.shape[1], k.shape[2]
        G = H // KVH
        idx = jax.lax.axis_index(axis)
        offset = idx * S_loc
        qg = q.reshape(B, KVH, G, Dh)
        scores = jnp.einsum("bhgd,bshd->bhgs", qg, k,
                            preferred_element_type=jnp.float32) \
            * (Dh ** -0.5)
        kpos = offset + jnp.arange(S_loc)
        scores = jnp.where(kpos[None, None, None, :] < valid_len,
                           scores, -1e30)
        m_loc = jnp.max(scores, axis=-1)                      # (B,KVH,G)
        p = jnp.exp(scores - m_loc[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
        # exact combine: global max, rescale partial sums
        m_glob = jax.lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, axis)
        o_glob = jax.lax.psum(o_loc * corr[..., None], axis)
        out = o_glob / jnp.maximum(l_glob[..., None], 1e-30)
        return out.reshape(B, H, Dh).astype(q.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None), P(dp, axis, None, None),
                  P(dp, axis, None, None), P()),
        out_specs=P(dp, None, None))
