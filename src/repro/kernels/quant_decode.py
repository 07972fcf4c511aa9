"""Pallas TPU fused-dequant decode attention (int8 KV cache).

The memory-bound decode roofline term is HBM cache traffic; an int8 cache
halves it — but only if dequantization happens HBM->VMEM inside the kernel
(an XLA-level dequant materializes a bf16 copy and wins nothing). This kernel
reads int8 K/V tiles + per-token scales into VMEM, dequantizes in-register,
and runs the usual streaming-softmax decode attention.

Grid = (B, KV, S/bs): the sequence axis is the innermost grid axis, so VMEM
holds one [bs, Dh] int8 tile of K and of V at a time (plus the streaming-
softmax state in scratch) at any cache length. Per-row valid lengths arrive
by scalar prefetch; tiles wholly past a row's length are neither copied nor
computed. The cache may be one layer's [B,KV,S,Dh] or the stacked pool
[L,B,KV,S,Dh] with a traced layer index, which arrives by scalar prefetch
too, so the index maps pick the layer's tiles straight from HBM and no
per-layer slice of the pool is ever made. Validated against
``ref.quant_decode_ref`` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def quantize_kv(k: jax.Array):
    """[...] bf16 -> (int8, f32 scale over the last dim)."""
    kf = k.astype(jnp.float32)
    scale = jnp.max(jnp.abs(kf), axis=-1, keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(kf / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def _kernel(pos_ref, layer_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
            m_scr, l_scr, acc_scr, *, bs: int, scale: float):
    # blocks: q [G,D]; k/v [bs,D] int8; ks/vs [KV,bs] (every head); o [G,D]
    bi, ki, si = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pos = pos_ref[bi]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(si * bs < pos)            # blocks wholly past pos hold no keys
    def _block():
        qv = q_ref[...].astype(jnp.float32) * scale             # [G, D]
        # Per-token scales multiply the logits and the probabilities rather
        # than the int8 rows, so they stay a lane vector [1, bs].
        s = jax.lax.dot_general(qv, k_ref[...].astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G,bs]
        s = s * ks_ref[pl.ds(ki, 1), :]
        slots = si * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slots < pos, s, NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p * vs_ref[pl.ds(ki, 1), :], v_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(si == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def quant_decode_attention(q: jax.Array, k8: jax.Array, k_scale: jax.Array,
                           v8: jax.Array, v_scale: jax.Array, pos, *,
                           layer=None, block_s: int = 512,
                           scale=None, interpret: bool = False):
    """q: [B,H,Dh] (one token); k8/v8: [B,KV,S,Dh] int8 with scales
    [B,KV,S] f32, or the stacked pool [L,B,KV,S,Dh] with scales [L,B,KV,S]
    and ``layer`` (an int32 scalar, may be traced) naming the layer to
    attend over; pos: valid length — scalar or [B] per-row vector
    (continuous batching); ``scale`` multiplies the logits (default
    Dh**-0.5). Returns [B,H,Dh]."""
    if k8.ndim == 4:          # one layer: a unit layer axis costs no copy
        k8, k_scale, v8, v_scale = k8[None], k_scale[None], v8[None], \
            v_scale[None]
        layer = 0
    b, h, dh = q.shape
    kv, smax = k8.shape[2], k8.shape[3]
    g = h // kv
    bs = min(block_s, smax)
    assert smax % bs == 0
    q4 = q.reshape(b, kv, g, dh)
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def live(bi, si, pos_ref):
        # Past pos, repeat the last live block: Pallas skips the copy.
        return jnp.minimum(si, jnp.maximum(pos_ref[bi] - 1, 0) // bs)

    def kv_block(bi, ki, si, pos_ref, layer_ref):
        return layer_ref[0], bi, ki, live(bi, si, pos_ref), 0

    def scale_block(bi, ki, si, pos_ref, layer_ref):
        # Every head's scales: Mosaic's last two block dims must be
        # (8,128)-aligned or whole, and KV is whole, so the [L,B,KV,S]
        # scales need no relayout; the kernel picks head ki's row.
        return layer_ref[0], bi, 0, live(bi, si, pos_ref)

    def head_block(bi, ki, si, pos_ref, layer_ref):
        return bi, ki, 0, 0

    kernel = functools.partial(_kernel, bs=bs,
                               scale=dh ** -0.5 if scale is None else scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, smax // bs),
            in_specs=[
                pl.BlockSpec((None, None, g, dh), head_block),
                pl.BlockSpec((None, None, None, bs, dh), kv_block),
                pl.BlockSpec((None, None, kv, bs), scale_block),
                pl.BlockSpec((None, None, None, bs, dh), kv_block),
                pl.BlockSpec((None, None, kv, bs), scale_block),
            ],
            out_specs=pl.BlockSpec((None, None, g, dh), head_block),
            scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, dh), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, layer_arr, q4, k8, k_scale.astype(jnp.float32), v8,
      v_scale.astype(jnp.float32))
    return out.reshape(b, h, dh)
