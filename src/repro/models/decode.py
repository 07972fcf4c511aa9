"""Serving paths: prefill (build cache) + single-token decode against a cache.

Cache layouts (leading dim scans over layers):
  dense/moe/vlm : {"k","v": [L,B,W,KV,Dh]}            W = window (ring) or max_len
  int8 (quant)  : {"k","v": [L,B,KV,W,Dh] int8, "k_scale","v_scale": [L,B,KV,W] f32}
  ssm           : {"h": [L,B,di,N] f32, "conv": [L,B,cw-1,di]}
  hybrid        : mamba2 state + shared-attn KV [nseg,B,W,KV,Dh]
  hybrid_moe    : mamba2 state {"h": [Lm,B,H,P,N] f32, "conv": [Lm,B,cw-1,C]}
                  of its Lm mamba layers beside the (int8) K/V of its La
                  attention layers, in one pool
  encdec        : self KV [L,...] + cross KV [L,B,Senc,KV,Dh] (built at prefill)

Batch is axis 1 of every leaf, so the serve engine scatters a prefilled row
into any pool alike. The int8 pool is stored in the layout that
``kernels.quant_decode`` reads (heads before positions), and decode carries
it whole through the layer loop: each layer writes its new token in place
at ``[layer, row, :, slot]`` and the kernel picks the layer's tiles out of
the stacked pool by a prefetched layer index. No layer of the pool is
sliced out, relaid, transposed or restacked, and no layer's weights are
copied out of the stack (``_quant_decode_layers``). The decode program
does not donate the pool (callers may reuse the pool they passed), so XLA
copies it once into the loop's carry; that is the one whole-pool copy of
a tick.

``pos`` is the number of tokens already in the cache; RoPE uses absolute
positions, so ring buffers (sliding window) stay correct without rotation.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import ssm as ssm_lib
from repro.models.model import (ModelCtx, _mlp_block, embed_tokens,
                                encoder_forward, head_logits, layer_runs,
                                moe_block, residual, rmsnorm)


# ------------------------------------------------------------------ cache init

def cache_spec(cfg: ArchConfig, batch: int, max_len: int,
               window: Optional[int] = None, enc_len: int = 0,
               dtype=jnp.bfloat16,
               quant: bool = False) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (ShapeDtypeStruct pytree, logical-axes pytree).

    ``quant=True``: int8 K/V with per-(token, head) f32 scales, heads before
    positions (the kernel's layout, module docstring) — halves the cache's
    HBM footprint/traffic; dequantization is fused HBM->VMEM by
    ``kernels.quant_decode`` on TPU (the XLA reference path dequantizes one
    layer slice at a time inside the layer loop)."""
    L = cfg.n_layers
    Lm = L
    if cfg.family == "hybrid_moe":
        L, Lm = cfg.n_of("attention"), cfg.n_of("mamba")
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    w = min(window or max_len, max_len)
    spec: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}
    kv_ax = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    fam = cfg.family
    if fam in ("dense", "vlm", "moe", "encdec", "hybrid_moe"):
        if quant:
            q_ax = ("layers", "batch", "kv_heads", "cache_seq", "head_dim")
            kvs = (L, batch, cfg.n_kv_heads, w, hd)
            spec["k"], spec["v"] = sds(kvs, jnp.int8), sds(kvs, jnp.int8)
            axes["k"] = axes["v"] = q_ax
            spec["k_scale"] = sds(kvs[:-1], jnp.float32)
            spec["v_scale"] = sds(kvs[:-1], jnp.float32)
            axes["k_scale"] = axes["v_scale"] = q_ax[:-1]
        else:
            kvs = (L, batch, w, cfg.n_kv_heads, hd)
            spec["k"], spec["v"] = sds(kvs), sds(kvs)
            axes["k"] = axes["v"] = kv_ax
    if fam == "encdec":
        ckvs = (L, batch, enc_len, cfg.n_kv_heads, hd)
        spec["ck"], spec["cv"] = sds(ckvs), sds(ckvs)
        axes["ck"] = axes["cv"] = kv_ax
    if fam in ("ssm", "hybrid", "hybrid_moe"):
        s = cfg.ssm
        di = s.expand * cfg.d_model
        if s.version == 1:
            spec["h"] = sds((Lm, batch, di, s.state_dim), jnp.float32)
            axes["h"] = ("layers", "batch", "ssm_inner", "ssm_state")
            conv_ch = di
        else:
            nh = di // s.head_dim
            spec["h"] = sds((Lm, batch, nh, s.head_dim, s.state_dim),
                            jnp.float32)
            axes["h"] = ("layers", "batch", "ssm_inner", None, "ssm_state")
            conv_ch = di + 2 * s.state_dim
        spec["conv"] = sds((Lm, batch, s.conv_width - 1, conv_ch))
        axes["conv"] = ("layers", "batch", None, "ssm_inner")
    if fam == "hybrid":
        nseg = cfg.n_layers // cfg.shared_attn_every
        kvs = (max(nseg, 1), batch, w, cfg.n_kv_heads, hd)
        spec["k"], spec["v"] = sds(kvs), sds(kvs)
        axes["k"] = axes["v"] = kv_ax
    return spec, axes


def init_cache(cfg, batch, max_len, window=None, enc_len=0, dtype=jnp.bfloat16,
               quant=False):
    spec, _ = cache_spec(cfg, batch, max_len, window, enc_len, dtype, quant)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def quantize_cache(cache):
    """Full-precision attention cache [L,B,W,KV,Dh] -> the int8 pool's layout
    [L,B,KV,W,Dh], k/v quantized per (token, head) with scales [L,B,KV,W];
    other leaves (encdec's cross ck/cv) stay as they are."""
    from repro.kernels.quant_decode import quantize_kv
    out = dict(cache)
    for name in ("k", "v"):
        out[name], out[name + "_scale"] = quantize_kv(
            jnp.swapaxes(cache[name], 2, 3))
    return out


# ------------------------------------------------------------------ helpers

def _qkv(cfg, p, hn, prefix=""):
    q = jnp.einsum("bsd,dhk->bshk", hn, p[prefix + "wq"])
    k = jnp.einsum("bsd,dhk->bshk", hn, p[prefix + "wk"])
    v = jnp.einsum("bsd,dhk->bshk", hn, p[prefix + "wv"])
    if cfg.qkv_bias and (prefix + "bq") in p:
        q, k, v = q + p[prefix + "bq"], k + p[prefix + "bk"], v + p[prefix + "bv"]
    return q, k, v


def _rope_qkv(cfg, p, h, pos, prefix=""):
    """Norm, q/k/v projections and RoPE of one token. h: [B,1,d]; pos
    scalar or [B]. Returns q, k, v [B,1,heads,Dh] and pos as int32."""
    hn = rmsnorm(h, p[prefix + "ln_attn"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, hn, prefix)
    pos = jnp.asarray(pos, jnp.int32)
    if cfg.use_rope:
        posv = pos[:, None] if pos.ndim == 1 else \
            jnp.full((1, 1), pos, jnp.int32)
        q = attn_lib.rope(q, posv, cfg.rope_theta)
        k = attn_lib.rope(k, posv, cfg.rope_theta)
    return q, k, v, pos


def _attn_decode_block(cfg, p, h, ck, cv, pos, window, prefix=""):
    """One-token self-attention vs cache. h: [B,1,d]. Returns h', new (ck, cv).

    ``pos`` is a scalar shared by every row or a ``[B]`` vector of per-row
    positions (continuous batching).
    """
    b, w = ck.shape[0], ck.shape[1]
    q, k, v, pos = _rope_qkv(cfg, p, h, pos, prefix)
    slot = pos % w if window else jnp.minimum(pos, w - 1)

    def write(buf, val):
        """Scatter one token per row at ``slot``. val: [B,1,...]."""
        if pos.ndim == 1:
            return buf.at[jnp.arange(b), slot].set(val[:, 0])
        return jax.lax.dynamic_update_slice_in_dim(buf, val, slot, axis=1)

    ck = write(ck, k.astype(ck.dtype))
    cv = write(cv, v.astype(cv.dtype))
    o = attn_lib.attend_decode(q, ck, cv, pos=pos + 1, ring=window is not None,
                               scale=cfg.attn_scale)
    out = jnp.einsum("bshk,hkd->bsd", o, p[prefix + "wo"])
    return residual(cfg, h, out), ck, cv


def _attn_decode_quant(cfg, p, h, pool, layer, pos, window, kv_kernel):
    """One-token self-attention of layer ``layer`` against the whole int8
    pool (module docstring). Returns the attention's output (before the
    residual) and the pool with this token's int8 K/V and scales written at
    ``[layer, row, :, slot]``.

    ``kv_kernel``: "pallas" (fused HBM->VMEM dequant kernel), "interpret"
    (the same kernel in Pallas interpret mode, CPU-safe) or "xla" (the
    reference: dequantize this layer's slice; also ring windows)."""
    from repro.kernels.quant_decode import quant_decode_attention, quantize_kv
    b, w = pool["k"].shape[1], pool["k"].shape[3]
    q, k, v, pos = _rope_qkv(cfg, p, h, pos)
    slot = pos % w if window else jnp.minimum(pos, w - 1)
    # One index per (row, head), so the update window is at most the
    # minor Dh axis and the scatter keeps the pool in the kernel's layout.
    rows = jnp.arange(b)[:, None]
    heads = jnp.arange(k.shape[2])[None, :]
    slot = jnp.broadcast_to(slot, (b,))[:, None]
    k8, ks = quantize_kv(k[:, 0])                    # [B,KV,Dh], [B,KV]
    v8, vs = quantize_kv(v[:, 0])
    pool = {name: pool[name].at[layer, rows, heads, slot].set(
                val, unique_indices=True)
            for name, val in (("k", k8), ("v", v8), ("k_scale", ks),
                              ("v_scale", vs))}
    if kv_kernel != "xla" and window is None:
        o = quant_decode_attention(
            q[:, 0], pool["k"], pool["k_scale"], pool["v"], pool["v_scale"],
            pos + 1, layer=layer, block_s=128 if w % 128 == 0 else w,
            scale=cfg.attn_scale, interpret=kv_kernel == "interpret")[:, None]
    else:
        def dequant(name):                           # -> [B,W,KV,Dh]
            x = pool[name][layer].astype(jnp.float32) \
                * pool[name + "_scale"][layer][..., None]
            return jnp.swapaxes(x, 1, 2).astype(k.dtype)
        # The barrier keeps the [B,W,KV,Dh] slices: folded into the
        # attention dots, the swap leaves a bf16 dot contracting a
        # non-minor axis, which XLA's CPU runtime cannot execute.
        kd, vd = jax.lax.optimization_barrier((dequant("k"), dequant("v")))
        o = attn_lib.attend_decode(q, kd, vd, pos=pos + 1,
                                   ring=window is not None,
                                   scale=cfg.attn_scale)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), pool


def _cross_decode_block(cfg, p, h, ck, cv, enc_len):
    hn = rmsnorm(h, p["cln_attn"], cfg.norm_eps)
    q, _, _ = _qkv(cfg, p, hn, "c")
    o = attn_lib.attend_decode(q, ck, cv, pos=enc_len)
    return h + jnp.einsum("bshk,hkd->bsd", o, p["cwo"])


def _fill_ring(k_seq, w, window):
    """[B,S,KV,Dh] -> ring buffer [B,w,KV,Dh] holding the last w positions at
    slot = pos % w (window) or the first w positions (full cache)."""
    s = k_seq.shape[1]
    if not window or s <= w:
        pad = w - min(s, w)
        out = k_seq[:, :w]
        if pad:
            out = jnp.pad(out, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return out
    tail = k_seq[:, -w:]                             # positions s-w .. s-1
    slots = (jnp.arange(s - w, s)) % w
    buf = jnp.zeros((k_seq.shape[0], w) + k_seq.shape[2:], k_seq.dtype)
    return buf.at[:, slots].set(tail)


# ------------------------------------------------------------------ prefill

def prefill(cfg: ArchConfig, params, batch, cache, ctx: ModelCtx):
    """Run the prompt, fill the cache. Returns (last-position logits, cache)."""
    xp, yp = params["x"], params["y"]
    tokens = batch["tokens"]
    b, S = tokens.shape
    pos = jnp.arange(S)
    h = embed_tokens(cfg, xp, tokens, batch.get("prefix_embeds"))
    fam = cfg.family
    w = cache["k"].shape[2] if "k" in cache else 0
    window = ctx.window

    if fam in ("dense", "vlm", "moe", "encdec"):
        enc_out = None
        if fam == "encdec":
            enc_out = encoder_forward(cfg, xp, batch["enc_embeds"], ctx)

        def body(carry, lp):
            hh = carry
            lp = jax.lax.optimization_barrier(lp)   # see model._scan_layers
            hn = rmsnorm(hh, lp["ln_attn"], cfg.norm_eps)
            q, k, v = _qkv(cfg, lp, hn)
            q = attn_lib.rope(q, pos, cfg.rope_theta)
            k = attn_lib.rope(k, pos, cfg.rope_theta)
            if ctx.kind == "prefill" and S > 4096:
                o = attn_lib.attend_flash(q, k, v, causal=True, window=window,
                                          chunk=ctx.attn_chunk)
            else:
                o = attn_lib.attend_full(q, k, v, causal=True, window=window)
            hh = hh + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
            ys = {"k": _fill_ring(k, w, window), "v": _fill_ring(v, w, window)}
            if fam == "encdec":
                hn2 = rmsnorm(hh, lp["cln_attn"], cfg.norm_eps)
                cq = jnp.einsum("bsd,dhk->bshk", hn2, lp["cwq"])
                ck = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cwk"])
                cv = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cwv"])
                if ck.shape[1] > ctx.attn_chunk:
                    o2 = attn_lib.attend_flash(cq, ck, cv, causal=False,
                                               chunk=ctx.attn_chunk)
                else:
                    o2 = attn_lib.attend_full(cq, ck, cv, causal=False)
                hh = hh + jnp.einsum("bshk,hkd->bsd", o2, lp["cwo"])
                ys["ck"], ys["cv"] = ck.astype(cache["ck"].dtype), \
                    cv.astype(cache["cv"].dtype)
            hh = _mlp_block(cfg, lp, hh, ctx)
            return hh, jax.tree.map(lambda a: a, ys)

        h, ys = jax.lax.scan(body, h, xp["layers"])
        cache = dict(cache)
        cache["k"] = ys["k"].astype(cache["k"].dtype)
        cache["v"] = ys["v"].astype(cache["v"].dtype)
        if fam == "encdec":
            cache["ck"], cache["cv"] = ys["ck"], ys["cv"]

    elif fam == "ssm":
        def body(carry, lp):
            lp = jax.lax.optimization_barrier(lp)   # see model._scan_layers
            hn = rmsnorm(carry, lp["ln"], cfg.norm_eps)
            y, (hst, conv) = ssm_lib.mamba1_seq(cfg, lp, hn, chunk=ctx.ssm_chunk)
            return carry + y, {"h": hst, "conv": conv}
        h, ys = jax.lax.scan(body, h, xp["layers"])
        cache = {"h": ys["h"], "conv": ys["conv"].astype(cache["conv"].dtype)}

    elif fam == "hybrid":
        h, cache = _hybrid_prefill(cfg, xp, h, cache, ctx, pos, w, window)
    elif fam == "hybrid_moe":
        h, cache = _pattern_prefill(cfg, xp, h, cache, ctx, pos, w, window)
    else:
        raise ValueError(fam)

    logits = head_logits(cfg, yp, h[:, -1:], xp["embed"])
    return logits, cache


def _pattern_prefill(cfg, xp, h, cache, ctx, pos, w, window):
    """hybrid_moe's prompt: one scan per run of the pattern, each with the
    barrier on the stacked weights and the layer indexed after it (see
    ``_quant_decode_layers``); returns h and the mamba states and the
    attention K/V of the layers, stacked per kind."""
    out = {"h": [], "conv": [], "k": [], "v": []}
    for kind, lo, n, j0 in layer_runs(cfg):
        def body(carry, i, kind=kind, lo=lo, j0=j0):
            (mix, ffn), i = jax.lax.optimization_barrier(
                ((xp[kind], xp["layers"]), i))
            mp = jax.tree.map(lambda a: a[j0 + i], mix)
            if kind == "mamba":
                with jax.named_scope("prefill/mamba"):
                    hn = rmsnorm(carry, mp["ln"], cfg.norm_eps)
                    y, (hst, conv) = ssm_lib.mamba2_seq(
                        cfg, mp, hn, chunk=ctx.ssm_chunk)
                    ys = {"h": hst, "conv": conv.astype(cache["conv"].dtype)}
            else:
                with jax.named_scope("prefill/attn"):
                    hn = rmsnorm(carry, mp["ln_attn"], cfg.norm_eps)
                    q, k, v = _qkv(cfg, mp, hn)
                    if cfg.use_rope:
                        q = attn_lib.rope(q, pos, cfg.rope_theta)
                        k = attn_lib.rope(k, pos, cfg.rope_theta)
                    if k.shape[1] > ctx.attn_chunk:
                        o = attn_lib.attend_flash(
                            q, k, v, causal=True, window=window,
                            chunk=ctx.attn_chunk, scale=cfg.attn_scale)
                    else:
                        o = attn_lib.attend_full(q, k, v, causal=True,
                                                 window=window,
                                                 scale=cfg.attn_scale)
                    y = jnp.einsum("bshk,hkd->bsd", o, mp["wo"])
                    ys = {"k": _fill_ring(k, w, window).astype(
                              cache["k"].dtype),
                          "v": _fill_ring(v, w, window).astype(
                              cache["v"].dtype)}
            carry = residual(cfg, carry, y)
            with jax.named_scope("prefill/moe"):
                carry, _ = moe_block(cfg, ffn, carry, layer=lo + i)
            return carry, ys
        h, ys = jax.lax.scan(body, h, jnp.arange(n, dtype=jnp.int32))
        for name, a in ys.items():
            out[name].append(a)
    new = dict(cache)
    new.update({name: jnp.concatenate(a, 0) for name, a in out.items()})
    return h, new


def _hybrid_prefill(cfg, xp, h, cache, ctx, pos, w, window):
    every = cfg.shared_attn_every
    nseg, rem = divmod(cfg.n_layers, every)
    layers = xp["layers"]

    def mamba_body(carry, lp):
        hn = rmsnorm(carry, lp["ln"], cfg.norm_eps)
        y, (hst, conv) = ssm_lib.mamba2_seq(cfg, lp, hn, chunk=ctx.ssm_chunk)
        return carry + y, {"h": hst, "conv": conv}

    def seg_body(carry, seg_params):
        hh, ys = jax.lax.scan(mamba_body, carry, seg_params)
        hn = rmsnorm(hh, xp["shared"]["ln_attn"], cfg.norm_eps)
        q, k, v = _qkv(cfg, xp["shared"], hn)
        q = attn_lib.rope(q, pos, cfg.rope_theta)
        k = attn_lib.rope(k, pos, cfg.rope_theta)
        if k.shape[1] > ctx.attn_chunk:
            o = attn_lib.attend_flash(q, k, v, causal=True, window=window,
                                      chunk=ctx.attn_chunk)
        else:
            o = attn_lib.attend_full(q, k, v, causal=True, window=window)
        hh = hh + jnp.einsum("bshk,hkd->bsd", o, xp["shared"]["wo"])
        hh = _mlp_block(cfg, xp["shared"], hh, ctx)
        ys.update({"k": _fill_ring(k, w, window), "v": _fill_ring(v, w, window)})
        return hh, ys

    states_h, states_c = [], []
    ks, vs = [], []
    if nseg:
        seg_stack = jax.tree.map(
            lambda a: a[: nseg * every].reshape((nseg, every) + a.shape[1:]),
            layers)
        h, ys = jax.lax.scan(seg_body, h, seg_stack)
        states_h.append(ys["h"].reshape((-1,) + ys["h"].shape[2:]))
        states_c.append(ys["conv"].reshape((-1,) + ys["conv"].shape[2:]))
        ks.append(ys["k"])
        vs.append(ys["v"])
    if rem:
        tail = jax.tree.map(lambda a: a[nseg * every:], layers)
        h, ys = jax.lax.scan(mamba_body, h, tail)
        states_h.append(ys["h"])
        states_c.append(ys["conv"])
    new = dict(cache)
    new["h"] = jnp.concatenate(states_h, 0)
    new["conv"] = jnp.concatenate(states_c, 0).astype(cache["conv"].dtype)
    if ks:
        new["k"] = ks[0].astype(cache["k"].dtype)
        new["v"] = vs[0].astype(cache["v"].dtype)
    return h, new


# ------------------------------------------------------------------ decode

def decode_step(cfg: ArchConfig, params, cache, token, pos, ctx: ModelCtx):
    """token: [B,1] int32; pos: int32 tokens already cached — scalar (all rows
    at the same position) or [B] per-row (continuous batching).
    Returns (logits [B,1,V], new cache)."""
    xp, yp = params["x"], params["y"]
    h = embed_tokens(cfg, xp, token, None)
    fam = cfg.family
    window = ctx.window
    new = dict(cache)

    if fam == "hybrid_moe":
        h, new = _pattern_decode_layers(cfg, xp, h, cache, pos, window, ctx)

    elif fam in ("dense", "vlm", "moe", "encdec") and "k_scale" in cache:
        h, new = _quant_decode_layers(cfg, xp, h, cache, pos, window, ctx)

    elif fam in ("dense", "vlm", "moe", "encdec"):
        enc_len = cache["ck"].shape[2] if fam == "encdec" else 0

        def body(carry, xs):
            xs = jax.lax.optimization_barrier(xs)   # see model._scan_layers
            lp = xs["p"]
            hh, ck, cv = _attn_decode_block(cfg, lp, carry, xs["k"],
                                            xs["v"], pos, window)
            ys = {"k": ck, "v": cv}
            if fam == "encdec":
                hh = _cross_decode_block(cfg, lp, hh, xs["ck"], xs["cv"], enc_len)
            hh = _mlp_block(cfg, lp, hh, ctx)
            return hh, ys

        xs = {"p": xp["layers"], "k": cache["k"], "v": cache["v"]}
        if fam == "encdec":
            xs["ck"], xs["cv"] = cache["ck"], cache["cv"]
        h, ys = jax.lax.scan(body, h, xs)
        new["k"], new["v"] = ys["k"], ys["v"]

    elif fam == "ssm":
        def body(carry, xs):
            xs = jax.lax.optimization_barrier(xs)   # see model._scan_layers
            hn = rmsnorm(carry, xs["p"]["ln"], cfg.norm_eps)
            y, (hst, conv) = ssm_lib.mamba1_decode(cfg, xs["p"], hn, xs["h"],
                                                   xs["conv"])
            return carry + y, {"h": hst, "conv": conv}
        h, ys = jax.lax.scan(body, h, {"p": xp["layers"], "h": cache["h"],
                                       "conv": cache["conv"]})
        new["h"], new["conv"] = ys["h"], ys["conv"]

    elif fam == "hybrid":
        h, new = _hybrid_decode(cfg, xp, h, cache, pos, window, ctx)
    else:
        raise ValueError(fam)

    return head_logits(cfg, yp, h, xp["embed"]), new


def _quant_decode_layers(cfg, xp, h, cache, pos, window, ctx):
    """The layer loop over an int8 pool: the pool rides in the carry beside
    h, and the loop runs over the layer index (and encdec's bf16 cross K/V).

    The barrier (see model._scan_layers) holds the stacked weights with the
    layer index, and the layer is indexed after it, so each dot reads its
    layer's weights in place; a barrier on the indexed layer would copy
    every layer's weights out of the stack once a tick."""
    enc_len = cache["ck"].shape[2] if cfg.family == "encdec" else 0

    def body(carry, xs):
        hh, pool = carry
        layers, layer = jax.lax.optimization_barrier((xp["layers"],
                                                      xs["layer"]))
        lp = jax.tree.map(lambda a: a[layer], layers)
        out, pool = _attn_decode_quant(cfg, lp, hh, pool, layer, pos,
                                       window, ctx.kv_kernel)
        hh = hh + out
        if cfg.family == "encdec":
            hh = _cross_decode_block(cfg, lp, hh, xs["ck"], xs["cv"], enc_len)
        return (_mlp_block(cfg, lp, hh, ctx), pool), None

    xs = {"layer": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
    if cfg.family == "encdec":
        xs["ck"], xs["cv"] = cache["ck"], cache["cv"]
    pool = {n: cache[n] for n in ("k", "v", "k_scale", "v_scale")}
    (h, pool), _ = jax.lax.scan(body, (h, pool), xs)
    return h, {**cache, **pool}


def _pattern_decode_layers(cfg, xp, h, cache, pos, window, ctx):
    """hybrid_moe's decode tick: one scan per run of the pattern, with the
    whole pool (the mamba state and conv state, and the attention K/V,
    int8 or not) riding in the carry beside h, as in
    ``_quant_decode_layers``: a mamba layer updates its state in place at
    its index, an attention layer writes its token's K/V at its index.
    The returned pool also holds ``expert_tokens`` [L, n_experts]: the
    tokens routed to each held expert of each layer in this tick, over
    every row of the batch (an empty slot's row too)."""
    quant = "k_scale" in cache
    names = ("h", "conv", "k", "v") + (("k_scale", "v_scale") if quant
                                       else ())
    pool = {n: cache[n] for n in names}
    routed = []
    for kind, lo, n, j0 in layer_runs(cfg):
        def body(carry, i, kind=kind, lo=lo, j0=j0):
            hh, pool = carry
            (mix, ffn), i = jax.lax.optimization_barrier(
                ((xp[kind], xp["layers"]), i))
            j = j0 + i
            mp = jax.tree.map(lambda a: a[j], mix)
            if kind == "mamba":
                with jax.named_scope("decode/mamba"):
                    hn = rmsnorm(hh, mp["ln"], cfg.norm_eps)
                    y, (hst, conv) = ssm_lib.mamba2_decode(
                        cfg, mp, hn, pool["h"][j], pool["conv"][j])
                    pool = {**pool, "h": pool["h"].at[j].set(hst),
                            "conv": pool["conv"].at[j].set(
                                conv.astype(pool["conv"].dtype))}
                    hh = residual(cfg, hh, y)
            elif quant:
                with jax.named_scope("decode/attn"):
                    y, kv = _attn_decode_quant(cfg, mp, hh, pool, j, pos,
                                               window, ctx.kv_kernel)
                    pool = {**pool, **kv}
                    hh = residual(cfg, hh, y)
            else:
                with jax.named_scope("decode/attn"):
                    hh, ck, cv = _attn_decode_block(
                        cfg, mp, hh, pool["k"][j], pool["v"][j], pos, window)
                    pool = {**pool, "k": pool["k"].at[j].set(ck),
                            "v": pool["v"].at[j].set(cv)}
            with jax.named_scope("decode/moe"):
                hh, sizes = moe_block(cfg, ffn, hh, layer=lo + i)
            return (hh, pool), sizes
        (h, pool), sizes = jax.lax.scan(body, (h, pool),
                                        jnp.arange(n, dtype=jnp.int32))
        routed.append(sizes)
    return h, {**cache, **pool, "expert_tokens": jnp.concatenate(routed, 0)}


def _hybrid_decode(cfg, xp, h, cache, pos, window, ctx):
    every = cfg.shared_attn_every
    nseg, rem = divmod(cfg.n_layers, every)

    def mamba_body(carry, xs):
        hn = rmsnorm(carry, xs["p"]["ln"], cfg.norm_eps)
        y, (hst, conv) = ssm_lib.mamba2_decode(cfg, xs["p"], hn, xs["h"],
                                               xs["conv"])
        return carry + y, {"h": hst, "conv": conv}

    def seg_body(carry, xs):
        hh, ys = jax.lax.scan(mamba_body, carry, xs["m"])
        hh, ck, cv = _attn_decode_block(cfg, xp["shared"], hh, xs["k"], xs["v"],
                                        pos, window)
        hh = _mlp_block(cfg, xp["shared"], hh, ctx)
        ys.update({"k": ck, "v": cv})
        return hh, ys

    layers = xp["layers"]
    new = dict(cache)
    hs, cs = [], []
    if nseg:
        seg_m = jax.tree.map(
            lambda a: a[: nseg * every].reshape((nseg, every) + a.shape[1:]),
            layers)
        mstate = {
            "p": seg_m,
            "h": cache["h"][: nseg * every].reshape(
                (nseg, every) + cache["h"].shape[1:]),
            "conv": cache["conv"][: nseg * every].reshape(
                (nseg, every) + cache["conv"].shape[1:]),
        }
        h, ys = jax.lax.scan(seg_body, h,
                             {"m": mstate, "k": cache["k"], "v": cache["v"]})
        hs.append(ys["h"].reshape((-1,) + ys["h"].shape[2:]))
        cs.append(ys["conv"].reshape((-1,) + ys["conv"].shape[2:]))
        new["k"], new["v"] = ys["k"], ys["v"]
    if rem:
        tail = {"p": jax.tree.map(lambda a: a[nseg * every:], layers),
                "h": cache["h"][nseg * every:], "conv": cache["conv"][nseg * every:]}
        h, ys = jax.lax.scan(mamba_body, h, tail)
        hs.append(ys["h"])
        cs.append(ys["conv"])
    new["h"] = jnp.concatenate(hs, 0)
    new["conv"] = jnp.concatenate(cs, 0)
    return h, new
