"""Attention primitives: GQA/MQA/MHA with RoPE, optional QKV bias, optional
sliding window; full path (train, S<=8k), flash-chunked path (long prefill),
and decode-vs-cache path (flash-decode friendly).

GQA is computed in grouped form — q reshaped to [B,S,KV,G,Dh] and einsummed
directly against unexpanded K/V — so repeated K/V heads are never
materialized (at 64q/8kv heads that expansion costs 8x the KV bytes).
Activations are sequence-sharded (q's S dim over `model`), so scores shard
over Sq while K/V stay whole; GSPMD inserts the seq all-gathers.

These are the pure-jnp reference paths used by the XLA/GSPMD pipeline; the
Pallas kernel in ``repro.kernels.flash_attention`` mirrors ``attend_flash``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, Dh]; pos: [..., S] int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs          # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                          # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def _grouped(q: jax.Array, kv_heads: int) -> jax.Array:
    """[B,S,H,Dh] -> [B,S,KV,G,Dh]."""
    b, s, h, dh = q.shape
    return q.reshape(b, s, kv_heads, h // kv_heads, dh)


def _mask(sq: int, sk: int, causal: bool, window: Optional[int],
          q_offset=0, k_offset=0):
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk) + k_offset
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def attend_full(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool = True, window: Optional[int] = None,
                q_offset: int = 0, scale: Optional[float] = None) -> jax.Array:
    """Plain softmax attention. q: [B,Sq,H,Dh]; k,v: [B,Sk,KV,Dh].
    ``scale`` multiplies the logits (default Dh**-0.5)."""
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    # operands stay in input dtype (bf16 -> MXU); accumulation is f32 via
    # preferred_element_type, so no f32 copies of K/V are materialized.
    q5 = _grouped(q, kv) * jnp.asarray(scale or dh ** -0.5, q.dtype)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q5, k,
                        preferred_element_type=jnp.float32)
    m = _mask(sq, sk, causal, window, q_offset)
    logits = jnp.where(m[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def attend_flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool = True, window: Optional[int] = None,
                 chunk: int = 1024, scale: Optional[float] = None) -> jax.Array:
    """Chunked (flash-style) attention over KV blocks: O(Sq*chunk) live scores.

    Forward-only usage (prefill); the train path uses attend_full under
    per-layer remat.
    """
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    if sk <= chunk:
        return attend_full(q, k, v, causal=causal, window=window, scale=scale)
    assert sk % chunk == 0, (sk, chunk)
    nkv = sk // chunk
    g = h // kv
    q5 = _grouped(q, kv) * jnp.asarray(scale or dh ** -0.5, q.dtype)
    qpos = jnp.arange(sq)

    kc = k.reshape(b, nkv, chunk, kv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nkv, chunk, kv, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, idx = xs
        kpos = idx * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", q5, kb,
                            preferred_element_type=jnp.float32)
        msk = jnp.ones((sq, chunk), bool)
        if causal:
            msk &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            msk &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(msk[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kv, g, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (kc, vc, jnp.arange(nkv)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dh)
    return out.astype(q.dtype)


def attend_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                  pos: jax.Array, ring: bool = False,
                  scale: Optional[float] = None) -> jax.Array:
    """One-token attention vs a cache.

    q: [B,1,H,Dh]; k_cache/v_cache: [B,Smax,KV,Dh]; pos: count of valid tokens
    *including* the current one — a scalar shared by every row, or a ``[B]``
    vector for per-row positions (continuous batching). With ``ring=True`` the
    cache is a ring buffer (sliding window); positions were RoPE'd at write
    time so slot order is irrelevant.
    """
    b, smax, kv, dh = k_cache.shape
    h = q.shape[2]
    q5 = _grouped(q, kv) * jnp.asarray(scale or dh ** -0.5, q.dtype)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q5.astype(k_cache.dtype), k_cache,
                        preferred_element_type=jnp.float32)
    slots = jnp.arange(smax)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        valid = slots < jnp.minimum(pos, smax)                   # [Smax]
        valid = valid[None, None, None, None, :]
    else:
        valid = slots[None, :] < jnp.minimum(pos, smax)[:, None]  # [B,Smax]
        valid = valid[:, None, None, None, :]
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dh).astype(q.dtype)
