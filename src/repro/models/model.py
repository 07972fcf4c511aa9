"""Unified model covering all assigned architecture families.

Param pytree layout (bilevel split is structural):
  {"x": {"embed", "layers", ["shared"], ["encoder"]},      # UL variable (backbone)
   "y": {"final_norm", "head"}}                            # LL variable (head)

All stacks scan over stacked layer params with per-layer remat (train), so HLO
size is O(1) in depth. Families:
  dense/vlm  : GQA attention + gated MLP (optional qkv bias / window / prefix fusion)
  moe        : GQA attention + top-k MoE (optional shared FFN)
  ssm        : mamba1 mixer only
  hybrid     : mamba2 mixers + ONE weight-tied shared attention block every k layers
  hybrid_moe : a published pattern of mamba2 and GQA layers (``layer_types``),
               each with its own weights and an MoE block of held experts
  encdec     : whisper-style encoder (stubbed frontend embeds) + cross-attn decoder
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.params import ParamSpec
from repro.sharding import shard_act


@dataclasses.dataclass(frozen=True)
class ModelCtx:
    """Per-call context: sharding rules + attention/window/runtime options."""
    rules: Optional[dict] = None
    window: Optional[int] = None      # sliding-window attention (long-context)
    kind: str = "train"               # train | prefill | decode
    attn_chunk: int = 1024
    ssm_chunk: int = 256
    kv_kernel: str = "xla"            # int8-KV decode path: xla | pallas | interpret


# ------------------------------------------------------------------ specs

def _attn_specs(cfg: ArchConfig, L: int, prefix="") -> Dict[str, ParamSpec]:
    d, h, kv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ax = ("layers",) if L else ()
    shp = (L,) if L else ()
    s = {
        prefix + "ln_attn": ParamSpec(shp + (d,), ax + ("embed",), init="ones",
                                      dtype="float32"),
        prefix + "wq": ParamSpec(shp + (d, h, hd), ax + ("embed", "heads", "head_dim")),
        prefix + "wk": ParamSpec(shp + (d, kv, hd), ax + ("embed", "kv_heads", "head_dim")),
        prefix + "wv": ParamSpec(shp + (d, kv, hd), ax + ("embed", "kv_heads", "head_dim")),
        prefix + "wo": ParamSpec(shp + (h, hd, d), ax + ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s[prefix + "bq"] = ParamSpec(shp + (h, hd), ax + ("heads", "head_dim"), init="zeros")
        s[prefix + "bk"] = ParamSpec(shp + (kv, hd), ax + ("kv_heads", "head_dim"), init="zeros")
        s[prefix + "bv"] = ParamSpec(shp + (kv, hd), ax + ("kv_heads", "head_dim"), init="zeros")
    return s


def _mlp_specs(cfg: ArchConfig, L: int, d_ff: int) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    ax = ("layers",) if L else ()
    shp = (L,) if L else ()
    return {
        "ln_mlp": ParamSpec(shp + (d,), ax + ("embed",), init="ones", dtype="float32"),
        "wi": ParamSpec(shp + (d, d_ff), ax + ("embed", "mlp")),
        "wu": ParamSpec(shp + (d, d_ff), ax + ("embed", "mlp")),
        "wd": ParamSpec(shp + (d_ff, d), ax + ("mlp", "embed")),
    }


def model_specs(cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    L = cfg.n_layers
    x: Dict[str, Any] = {
        # vocab_in -> None everywhere: a vocab-sharded table turns every
        # embedding gather into cross-client all-reduces inside LOCAL steps
        # (measured: 20 MiB x microbatches x passes on the 2-pod mesh). The
        # table replicates over vocab; zero-mode FSDP shards its embed dim.
        "embed": ParamSpec((cfg.vocab, d), ("vocab_in", "embed")),
    }
    fam = cfg.family
    if fam in ("dense", "vlm"):
        layers = {**_attn_specs(cfg, L), **_mlp_specs(cfg, L, cfg.d_ff)}
    elif fam == "moe":
        layers = {**_attn_specs(cfg, L), **moe_lib.moe_specs(cfg, L)}
        layers["ln_mlp"] = ParamSpec((L, d), ("layers", "embed"), init="ones",
                                     dtype="float32")
    elif fam == "ssm":
        layers = ssm_lib.mamba1_specs(cfg, L)
    elif fam == "hybrid":
        layers = ssm_lib.mamba2_specs(cfg, L)
        x["shared"] = {**_attn_specs(cfg, 0), **_mlp_specs(cfg, 0, cfg.d_ff)}
    elif fam == "hybrid_moe":
        layers = {**moe_lib.moe_specs(cfg, L),
                  "ln_mlp": ParamSpec((L, d), ("layers", "embed"),
                                      init="ones", dtype="float32")}
        x["mamba"] = ssm_lib.mamba2_specs(cfg, cfg.n_of("mamba"))
        x["attn"] = _attn_specs(cfg, cfg.n_of("attention"))
    elif fam == "encdec":
        layers = {**_attn_specs(cfg, L), **_mlp_specs(cfg, L, cfg.d_ff)}
        layers.update(_attn_specs(cfg, L, prefix="c"))          # cross-attention
        x["encoder"] = {
            "layers": {**_attn_specs(cfg, cfg.encoder.n_layers),
                       **_mlp_specs(cfg, cfg.encoder.n_layers, cfg.d_ff)},
            "ln_out": ParamSpec((d,), ("embed",), init="ones", dtype="float32"),
        }
    else:
        raise ValueError(fam)
    x["layers"] = layers
    y = {"final_norm": ParamSpec((d,), ("embed",), init="ones",
                                 dtype="float32")}
    if not cfg.tie_embeddings:           # tied: the head is x["embed"]
        y["head"] = ParamSpec((d, cfg.vocab), ("embed", "vocab"))
    return {"x": x, "y": y}


# ------------------------------------------------------------------ primitives

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    # statistics accumulated in f32 via the dot (no f32 copy of x exists, so
    # autodiff/XLA residuals of the layer stay bf16), multiply in x.dtype.
    xx = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)[..., None]
    r = jax.lax.rsqrt(xx / x.shape[-1] + eps)
    return x * (r.astype(x.dtype) * w.astype(x.dtype))


def residual(cfg: ArchConfig, h: jax.Array, y: jax.Array) -> jax.Array:
    """h + y, the branch scaled by the residual multiplier where it has one."""
    if cfg.residual_multiplier == 1.0:
        return h + y
    return h + jnp.asarray(cfg.residual_multiplier, y.dtype) * y


def _attn_block(cfg: ArchConfig, p, h, ctx: ModelCtx, *, pos, causal=True,
                prefix="", kv_h=None, kv_pos=None):
    """Self- or cross-attention block. h: [B,S,d]. kv_h: source for K/V (cross)."""
    hn = rmsnorm(h, p[prefix + "ln_attn"], cfg.norm_eps)
    src = hn if kv_h is None else kv_h
    q = jnp.einsum("bsd,dhk->bshk", hn, p[prefix + "wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p[prefix + "wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p[prefix + "wv"])
    if cfg.qkv_bias and (prefix + "bq") in p:
        q = q + p[prefix + "bq"]
        k = k + p[prefix + "bk"]
        v = v + p[prefix + "bv"]
    if kv_h is None and cfg.use_rope:                     # RoPE for self-attn only
        q = attn_lib.rope(q, pos, cfg.rope_theta)
        kp = pos if kv_pos is None else kv_pos
        k = attn_lib.rope(k, kp, cfg.rope_theta)
    q = shard_act(q, ("batch", "seq", "heads", None), ctx.rules)
    s_kv = src.shape[1]
    if s_kv > ctx.attn_chunk:
        # chunked flash path: bounds live scores to O(Sq*chunk) in fwd AND bwd
        # (checkpointed chunk body), for train, prefill and cross-attention.
        o = attn_lib.attend_flash(q, k, v, causal=causal and kv_h is None,
                                  window=ctx.window, chunk=ctx.attn_chunk,
                                  scale=cfg.attn_scale)
    elif kv_h is not None:
        o = attn_lib.attend_full(q, k, v, causal=False, scale=cfg.attn_scale)
    else:
        o = attn_lib.attend_full(q, k, v, causal=causal, window=ctx.window,
                                 scale=cfg.attn_scale)
    out = jnp.einsum("bshk,hkd->bsd", o, p[prefix + "wo"])
    return residual(cfg, h, out), (k, v)


def _mlp_block(cfg: ArchConfig, p, h, ctx: ModelCtx):
    hn = rmsnorm(h, p["ln_mlp"], cfg.norm_eps)
    if cfg.family == "moe":
        # tokens enter the MoE block seq-UNsharded: dispatch from seq-sharded
        # tokens into expert-sharded buffers makes GSPMD all-reduce scatter
        # partials over `model` (measured 17 GiB wire on the 32k prefill);
        # localizing tokens first yields the classic expert all-to-all.
        hn = shard_act(hn, ("batch", None, "act_embed"), ctx.rules)
        out = moe_lib.apply_moe(cfg, p, hn)
        out = shard_act(out, ("batch", "seq", "act_embed"), ctx.rules)
    else:
        g = jnp.einsum("bsd,df->bsf", hn, p["wi"])
        u = jnp.einsum("bsd,df->bsf", hn, p["wu"])
        out = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["wd"])
    return h + out


def _transformer_layer(cfg, p, h, ctx, pos, *, causal=True, cross_src=None):
    h, _ = _attn_block(cfg, p, h, ctx, pos=pos, causal=causal)
    if cross_src is not None:
        h, _ = _attn_block(cfg, p, h, ctx, pos=pos, causal=False,
                           prefix="c", kv_h=cross_src)
    h = _mlp_block(cfg, p, h, ctx)
    return shard_act(h, ("batch", "seq", "act_embed"), ctx.rules)


def _scan_layers(body, stacked, h, remat: bool):
    fn = jax.checkpoint(body) if remat else body

    def step(carry, xs):
        # barrier: stops XLA from hoisting per-layer weight dtype-conversions
        # out of the loop (the CPU backend upcasts bf16 dot operands to f32;
        # hoisted, that materializes an f32 copy of EVERY layer's weights).
        xs = jax.lax.optimization_barrier(xs)
        return fn(carry, xs), None

    h, _ = jax.lax.scan(step, h, stacked)
    return h


# ------------------------------------------------------------------ features

def embed_tokens(cfg, xp, tokens, prefix_embeds):
    h = jnp.take(xp["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    if prefix_embeds is not None and cfg.n_prefix_embeds:
        npfx = prefix_embeds.shape[1]
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h[:, npfx:]], axis=1)
    return h


def encoder_forward(cfg, xp, enc_embeds, ctx: ModelCtx):
    """Whisper-style encoder over stubbed frame embeddings [B,Senc,d]."""
    ep = xp["encoder"]
    h = enc_embeds
    pos = jnp.arange(h.shape[1])

    def body(carry, lp):
        return _transformer_layer(cfg, lp, carry, ctx, pos, causal=False)

    h = _scan_layers(body, ep["layers"], h, remat=ctx.kind == "train")
    return rmsnorm(h, ep["ln_out"], cfg.norm_eps)


def features(cfg: ArchConfig, xp, batch: Dict[str, jax.Array],
             ctx: ModelCtx) -> jax.Array:
    """Backbone features [B,S,d] (everything except final norm + LM head)."""
    tokens = batch["tokens"]
    h = embed_tokens(cfg, xp, tokens, batch.get("prefix_embeds"))
    h = shard_act(h, ("batch", "seq", "act_embed"), ctx.rules)
    b, S = tokens.shape
    pos = jnp.arange(S)
    remat = ctx.kind == "train"
    fam = cfg.family

    if fam in ("dense", "vlm", "moe"):
        def body(carry, lp):
            return _transformer_layer(cfg, lp, carry, ctx, pos)
        h = _scan_layers(body, xp["layers"], h, remat)

    elif fam == "ssm":
        def body(carry, lp):
            hn = rmsnorm(carry, lp["ln"], cfg.norm_eps)
            y, _ = ssm_lib.mamba1_seq(cfg, lp, hn, chunk=ctx.ssm_chunk)
            out = carry + y
            return shard_act(out, ("batch", "seq", "act_embed"), ctx.rules)
        h = _scan_layers(body, xp["layers"], h, remat)

    elif fam == "hybrid":
        h = _hybrid_seq(cfg, xp, h, ctx, pos, remat)

    elif fam == "hybrid_moe":
        for kind, lo, n, j0 in layer_runs(cfg):
            def body(carry, lp, kind=kind):
                if kind == "mamba":
                    hn = rmsnorm(carry, lp["m"]["ln"], cfg.norm_eps)
                    y, _ = ssm_lib.mamba2_seq(cfg, lp["m"], hn,
                                              chunk=ctx.ssm_chunk)
                    carry = residual(cfg, carry, y)
                else:
                    carry, _ = _attn_block(cfg, lp["m"], carry, ctx, pos=pos)
                carry, _ = moe_block(cfg, lp["f"], carry)
                return shard_act(carry, ("batch", "seq", "act_embed"),
                                 ctx.rules)
            stack = {"m": jax.tree.map(lambda a: a[j0:j0 + n], xp[kind]),
                     "f": jax.tree.map(lambda a: a[lo:lo + n], xp["layers"])}
            h = _scan_layers(body, stack, h, remat)

    elif fam == "encdec":
        enc_out = encoder_forward(cfg, xp, batch["enc_embeds"], ctx)

        def body(carry, lp):
            return _transformer_layer(cfg, lp, carry, ctx, pos, cross_src=enc_out)
        h = _scan_layers(body, xp["layers"], h, remat)
    else:
        raise ValueError(fam)
    return h


def _hybrid_seq(cfg, xp, h, ctx, pos, remat):
    """zamba2: scan segments of `every` mamba2 layers; after each segment apply
    the single weight-tied shared attention+MLP block."""
    every = cfg.shared_attn_every
    L = cfg.n_layers
    nseg, rem = divmod(L, every)
    layers = xp["layers"]

    def mamba_body(carry, lp):
        hn = rmsnorm(carry, lp["ln"], cfg.norm_eps)
        y, _ = ssm_lib.mamba2_seq(cfg, lp, hn, chunk=ctx.ssm_chunk)
        out = carry + y
        return shard_act(out, ("batch", "seq", "act_embed"), ctx.rules)

    def seg_body(carry, seg_params):
        hh = _scan_layers(mamba_body, seg_params, carry, remat)
        hh = _transformer_layer(cfg, xp["shared"], hh, ctx, pos)
        return hh, None

    if nseg:
        seg_stack = jax.tree.map(
            lambda a: a[: nseg * every].reshape((nseg, every) + a.shape[1:]),
            layers)
        h, _ = jax.lax.scan(seg_body, h, seg_stack)
    if rem:
        tail = jax.tree.map(lambda a: a[nseg * every:], layers)
        h = _scan_layers(mamba_body, tail, h, remat)
    return h


def layer_runs(cfg: ArchConfig):
    """hybrid_moe's pattern as runs of consecutive layers of one kind:
    [(kind, first layer, count, index of its first layer in that kind's
    stack)], kind "mamba" or "attn"."""
    runs, seen = [], {"mamba": 0, "attn": 0}
    for i, t in enumerate(cfg.layer_types):
        kind = "mamba" if t == "mamba" else "attn"
        if runs and runs[-1][0] == kind:
            k, lo, n, j0 = runs[-1]
            runs[-1] = (k, lo, n + 1, j0)
        else:
            runs.append((kind, i, 1, seen[kind]))
        seen[kind] += 1
    return runs


def moe_block(cfg: ArchConfig, p, h, layer=None):
    """hybrid_moe's MLP: the held experts' part plus the shared expert, on
    the normed stream, as one residual branch. Returns (h', tokens routed
    to each held expert). With ``layer`` (serving), ``p`` is the whole
    stack and this the layer's index (see ``moe.held_experts``)."""
    if layer is not None:
        p = {k: a if k.startswith("we_") else a[layer] for k, a in p.items()}
    hn = rmsnorm(h, p["ln_mlp"], cfg.norm_eps)
    routed, sizes = moe_lib.held_experts(cfg, p, hn, layer)
    return residual(cfg, h, routed + moe_lib.shared_expert(p, hn)), sizes


def head_logits(cfg: ArchConfig, yp, feats: jax.Array,
                embed: Optional[jax.Array] = None) -> jax.Array:
    """Logits of the final-normed features; a tied model passes its
    embedding table as ``embed``."""
    h = rmsnorm(feats, yp["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", h, embed)
    else:
        out = jnp.einsum("bsd,dv->bsv", h, yp["head"])
    if cfg.logits_scaling != 1.0:
        out = out / jnp.asarray(cfg.logits_scaling, out.dtype)
    return out


def forward(cfg, params, batch, ctx: ModelCtx) -> jax.Array:
    return head_logits(cfg, params["y"], features(cfg, params["x"], batch, ctx),
                       params["x"]["embed"])
