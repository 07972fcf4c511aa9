"""Plain float32 forward pass of the llama-style decoder and its
encoder-decoder variant, written from the published architectures.

Blocks: RMSNorm (``x * rsqrt(mean(x^2) + eps) * w``), rotary position
embeddings that rotate the two halves of each head, grouped-query attention
with a causal mask (self) or none (encoder, cross-attention; no rotary
there), a SwiGLU MLP (``wd(silu(x wi) * (x wu))``) and an untied output head
behind a final RMSNorm. The encoder-decoder variant runs a bidirectional
encoder over frame embeddings, normalises its output, and gives every
decoder layer a cross-attention block between self-attention and the MLP.

Nothing here imports the system under test. Every matmul runs at HIGHEST
precision and every array is float32, whatever dtype the weights are held
in. Weights are the nested dict that ``weights.py`` lays out: ``x`` holds
the backbone (``embed``, stacked ``layers``, optional ``encoder``) and ``y``
the head (``final_norm``, ``head``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def ein(spec, *args):
    return jnp.einsum(spec, *(a.astype(F32) for a in args), precision=HI)


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


def rope(x, pos, theta):
    """x [..., S, H, D]; pos [S]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, causal, q_block=512):
    """q [B,Sq,H,D]; k, v [B,Sk,KV,D] -> [B,Sq,H,D]; heads share K/V in
    groups of H/KV. Queries go in blocks of ``q_block`` rows, which bounds
    the scores held at once."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, sq, kv, h // kv, d)
    out = []
    for s0 in range(0, sq, q_block):
        qb = q[:, s0:s0 + q_block]
        s = ein("bqkgd,bskd->bkgqs", qb, k) / jnp.sqrt(F32(d))
        if causal:
            qpos = s0 + jnp.arange(qb.shape[1])
            m = jnp.arange(k.shape[1])[None, :] <= qpos[:, None]
            s = jnp.where(m, s, -jnp.inf)
        out.append(ein("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, 1).reshape(b, sq, h, d)


def attn_block(c, p, h, pre="", src=None, causal=True):
    hn = rmsnorm(h, p[pre + "ln_attn"], c["norm_eps"])
    kv_in = hn if src is None else src
    q = ein("bsd,dhk->bshk", hn, p[pre + "wq"])
    k = ein("bsd,dhk->bshk", kv_in, p[pre + "wk"])
    v = ein("bsd,dhk->bshk", kv_in, p[pre + "wv"])
    if src is None:
        pos = jnp.arange(h.shape[1])
        q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    o = attention(q, k, v, causal and src is None)
    return h + ein("bshk,hkd->bsd", o, p[pre + "wo"])


def mlp_block(c, p, h):
    hn = rmsnorm(h, p["ln_mlp"], c["norm_eps"])
    g = ein("bsd,df->bsf", hn, p["wi"])
    u = ein("bsd,df->bsf", hn, p["wu"])
    return h + ein("bsf,fd->bsd", jax.nn.silu(g) * u, p["wd"])


def over_layers(body, stack, h):
    """Apply ``body(h, layer params)`` for each layer of a stacked dict."""
    return jax.lax.scan(lambda hh, p: (body(hh, p), None), h, stack)[0]


def encoder(c, xp, frames):
    ep = xp["encoder"]
    h = over_layers(lambda h, p: mlp_block(c, p, attn_block(
        c, p, h, causal=False)), ep["layers"], frames.astype(F32))
    return rmsnorm(h, ep["ln_out"], c["norm_eps"])


def features(c, xp, tokens, frames=None):
    """Backbone output [B, S, d] before the final norm and head."""
    h = jnp.take(xp["embed"], tokens, axis=0).astype(F32)
    enc = encoder(c, xp, frames) if frames is not None else None

    def body(h, p):
        h = attn_block(c, p, h)
        if enc is not None:
            h = attn_block(c, p, h, pre="c", src=enc)
        return mlp_block(c, p, h)
    return over_layers(body, xp["layers"], h)


def logits(c, yp, feats):
    return ein("bsd,dv->bsv", rmsnorm(feats, yp["final_norm"], c["norm_eps"]),
               yp["head"])


def next_token_xent(c, yp, feats, tokens):
    """Mean cross-entropy of predicting ``tokens[:, 1:]`` from the features
    of the positions before them."""
    lg = logits(c, yp, feats[:, :-1])
    lse = jax.scipy.special.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - tgt)
