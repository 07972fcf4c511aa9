"""Plain float32 logits of the Granite 4.0-H hybrid (Mamba-2 and attention
layers in a published pattern, an MoE block in every layer), written from
the published configuration and the equations of the Hugging Face model
(``modeling_granitemoehybrid.py``), for checking served tokens.

Per layer ``l`` of ``layer_types``, on one sequence:

    h = h + r * mixer_l(rmsnorm(h))
    h = h + r * (moe_l(rmsnorm'(h)) + shared_l(rmsnorm'(h)))

with ``r`` the residual multiplier; the embedding is multiplied by the
embedding multiplier, and the logits are the final-normed features times
the (tied) embedding table, divided by the logits scaling.

- Mamba-2 mixer: ``in_proj`` gives z, the conv input and dt; a causal
  depthwise convolution (width ``mamba_d_conv``, with bias) and SiLU give
  x, B, C; dt = softplus(dt + dt_bias), A = -exp(A_log). The recurrence
  runs as a plain scan over positions, one head-wise state [P, N] per head:
  ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T``, ``y_t = s_t C_t + D x_t``.
  Then the gated RMSNorm of ``y * silu(z)`` and ``out_proj``.
- Attention: grouped-query, causal, no positional encoding, logits scaled
  by the attention multiplier.
- MoE: the router scores every routed expert; each token keeps its top
  ``num_experts_per_tok`` with a softmax over those as gates. Only the
  experts the configuration holds (``first_expert`` onwards,
  ``num_local_experts`` of them) are computed, as SwiGLU experts weighted
  by their gates; the shared expert is a SwiGLU on every token.

Departures from the published model: multi-token prediction is not part of
this model; of the routed experts only the held ones are computed (the
configuration's cut, which the system makes too).

Nothing here imports the system under test. Every matmul runs at HIGHEST
precision and every array is float32, whatever dtype the weights are held
in: each layer is cast to float32 only while it runs, and the head runs in
vocab blocks, so that the model still fits beside it. ``lower`` selects the
control: every weight matrix and each layer's input rounded to float8
(e4m3, one scale per tensor) before the float32 arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
VOCAB_BLOCK = 16384
PAD = 512
Q_BLOCK = 512


def ein(spec, *args):
    return jnp.einsum(spec, *(a.astype(F32) for a in args), precision=HI)


def fp8(x):
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _cast(p, lower):
    return {k: fp8(a) if lower and a.ndim >= 2 else a.astype(F32)
            for k, a in p.items()}


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def mamba(c, p, x):
    """x [S, d] -> [S, d]."""
    di = c["mamba_expand"] * c["hidden_size"]
    n = c["mamba_n_groups"] * c["mamba_d_state"]
    nh, hp, cw = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_conv"]
    proj = ein("sd,de->se", x, p["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * n], proj[:, 2 * di + 2 * n:]
    padded = jnp.concatenate([jnp.zeros((cw - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[i:i + x.shape[0]] * p["conv_w"][i] for i in range(cw))
    xbc = silu(conv + p["conv_b"])
    xs = xbc[:, :di].reshape(-1, nh, hp)
    bm, cm = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + p["dt_b"])                   # [S, H]
    a = -jnp.exp(p["A_log"])                                # [H]

    def step(s, t):
        x_t, b_t, c_t, dt_t = t
        s = jnp.exp(dt_t * a)[:, None, None] * s + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, ein("hpn,n->hp", s, c_t)
    s0 = jnp.zeros((nh, hp, n), F32)
    _, y = jax.lax.scan(step, s0, (xs, bm, cm, dt))
    y = y + p["D"][:, None] * xs
    y = y.reshape(-1, di) * silu(z)
    y = rmsnorm(y, p["gate_ln"], c["rms_norm_eps"])
    return ein("se,ed->sd", y, p["out_proj"])


def attention(c, p, x):
    """Causal grouped-query attention without positional encoding; x
    [S, d] -> [S, d]. Queries go in blocks, which bounds the scores."""
    q = ein("sd,dhk->shk", x, p["wq"])
    k = ein("sd,dhk->shk", x, p["wk"])
    v = ein("sd,dhk->shk", x, p["wv"])
    s_len, h, dh = q.shape
    kv = k.shape[1]
    nq = min(Q_BLOCK, s_len)
    q = q.reshape(s_len // nq, nq, kv, h // kv, dh)

    def block(args):
        qb, s0 = args
        lg = ein("qkgd,skd->kgqs", qb, k) * c["attention_multiplier"]
        qpos = s0 + jnp.arange(nq)
        lg = jnp.where(jnp.arange(s_len)[None, :] <= qpos[:, None], lg,
                       -jnp.inf)
        return ein("kgqs,skd->qkgd", jax.nn.softmax(lg, -1), v)
    o = jax.lax.map(block, (q, jnp.arange(0, s_len, nq)))
    return ein("shk,hkd->sd", o.reshape(s_len, h, dh), p["wo"])


def moe(c, p, x):
    """The held experts' part of the routed MoE plus the shared expert;
    x [S, d] -> [S, d]. The held experts run one after another."""
    logits = ein("sd,de->se", x, p["router"])
    top, ids = jax.lax.top_k(logits, c["num_experts_per_tok"])
    gates = jax.nn.softmax(top, -1)

    def expert(out, i):
        w = jnp.sum(jnp.where(ids == c["first_expert"] + i, gates, 0.0), -1)
        g = ein("sd,df->sf", x, p["we_gate"][i])
        u = ein("sd,df->sf", x, p["we_up"][i])
        return out + w[:, None] * ein("sf,fd->sd", silu(g) * u,
                                      p["we_down"][i]), None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          jnp.arange(c["num_local_experts"]))
    hs = silu(ein("sd,df->sf", x, p["ws_gate"])) * \
        ein("sd,df->sf", x, p["ws_up"])
    return out + ein("sf,fd->sd", hs, p["ws_down"])


@functools.partial(jax.jit, static_argnums=(0, 1, 6))
def _layer(c, kind, mix, ffn, i, h, lower):
    c = dict(c)
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    ip, jp = i
    mp = _cast({k: a[jp] for k, a in mix.items()}, lower)
    fp = _cast({k: a[ip] for k, a in ffn.items()}, lower)
    if lower:
        h = fp8(h)
    if kind == "mamba":
        h = h + r * mamba(c, mp, rmsnorm(h, mp["ln"], eps))
    else:
        h = h + r * attention(c, mp, rmsnorm(h, mp["ln_attn"], eps))
    if lower:
        h = fp8(h)
    return h + r * moe(c, fp, rmsnorm(h, fp["ln_mlp"], eps))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_block(c, norm, table, feats, lower):
    c = dict(c)
    hn = rmsnorm(feats, norm.astype(F32), c["rms_norm_eps"])
    table = fp8(table) if lower else table
    if lower:
        hn = fp8(hn)
    return ein("sd,vd->sv", hn, table) / c["logits_scaling"]


def _static(c):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()
                        if not isinstance(v, dict)))


def logits_at(c, params, tokens, rows, lower=False):
    """float32 logits [len(rows), vocab] of sequence ``tokens`` at
    positions ``rows``."""
    ct = _static(c)
    xp, yp = params["x"], params["y"]
    # causal: padding after the sequence leaves the positions before it
    # alone; every sequence is padded to the pool's length, so each kind
    # of layer compiles once
    n = len(tokens)
    toks = np.zeros(c["serve"]["max_len"], np.int32)
    toks[:n] = tokens
    h = jnp.take(xp["embed"], jnp.asarray(toks), axis=0).astype(F32) * \
        c["embedding_multiplier"]
    seen = {"mamba": 0, "attention": 0}
    for i, t in enumerate(c["layer_types"]):
        kind = "mamba" if t == "mamba" else "attn"
        h = _layer(ct, kind, xp[kind], xp["layers"],
                   (jnp.int32(i), jnp.int32(seen[t])), h, lower)
        seen[t] += 1
    idx = np.zeros(-(-len(rows) // PAD) * PAD, np.int32)
    idx[:len(rows)] = rows
    feats = h[jnp.asarray(idx)]
    v = c["vocab_size"]
    blocks = [_head_block(ct, yp["final_norm"],
                          xp["embed"][s:s + VOCAB_BLOCK], feats, lower)
              for s in range(0, v, VOCAB_BLOCK)]
    return jnp.concatenate(blocks, -1)[:len(rows)]


def served_gaps(c, params, prompt, served, lower=False):
    """Gaps at each served position: the float32 reference's best logit
    minus its logit of the chosen token, in units of the standard deviation
    of the reference's logits there. ``lower``: the chosen token is the
    control's first choice instead of the served one."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits_at(c, params, seq, rows)
    if lower:
        chosen = jnp.argmax(logits_at(c, params, seq, rows, lower=True), -1)
    else:
        chosen = jnp.asarray(served)
    got = jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return np.asarray((jnp.max(ref, -1) - got) / jnp.std(ref, -1))
