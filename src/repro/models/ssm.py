"""Selective state-space layers.

- Mamba1 (falcon-mamba-7b): per-channel state, chunked associative scan.
- Mamba2 (zamba2): multi-head scalar-A SSD with the chunked dual form
  (intra-chunk quadratic + inter-chunk recurrence), which is both the honest
  FLOPs form and the memory-feasible one.

Both expose train/prefill paths (full sequence -> outputs [+ final state]) and
decode paths (single-token state update).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import ParamSpec


# ---------------------------------------------------------------- mamba1

def mamba1_specs(cfg: ArchConfig, n_layers: int) -> Dict[str, ParamSpec]:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    dtr = max(d // 16, 1)
    L = n_layers
    ax = ("layers",)
    return {
        "ln": ParamSpec((L, d), ax + ("embed",), init="ones", dtype="float32"),
        "in_proj": ParamSpec((L, d, 2 * di), ax + ("embed", "ssm_inner")),
        "conv_w": ParamSpec((L, s.conv_width, di), ax + ("conv", "ssm_inner")),
        "conv_b": ParamSpec((L, di), ax + ("ssm_inner",), init="zeros"),
        "x_proj": ParamSpec((L, di, dtr + 2 * s.state_dim),
                            ax + ("ssm_inner", None)),
        "dt_w": ParamSpec((L, dtr, di), ax + (None, "ssm_inner")),
        "dt_b": ParamSpec((L, di), ax + ("ssm_inner",), init="ssm_dt",
                          dtype="float32"),
        "A_log": ParamSpec((L, di, s.state_dim), ax + ("ssm_inner", "ssm_state"),
                           init="ssm_a", dtype="float32"),
        "D": ParamSpec((L, di), ax + ("ssm_inner",), init="ones", dtype="float32"),
        "out_proj": ParamSpec((L, di, d), ax + ("ssm_inner", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """x: [B,S,di]; w: [cw,di]; depthwise causal conv. Returns (y, new_state)
    where state holds the trailing cw-1 inputs."""
    cw = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(cw))
    new_state = xp[:, -(cw - 1):] if cw > 1 else state
    return jax.nn.silu(y + b), new_state


def _selective_scan_chunk(a: jax.Array, bx: jax.Array, h0: jax.Array):
    """Linear recurrence h_t = a_t * h_{t-1} + bx_t within one chunk via an
    associative scan. a, bx: [B, c, di, N]; h0: [B, di, N]."""
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl
    a_all, b_all = jax.lax.associative_scan(combine, (a, bx), axis=1)
    h = a_all * h0[:, None] + b_all                 # [B, c, di, N]
    return h, h[:, -1]


def mamba1_seq(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
               h0: Optional[jax.Array] = None, conv0=None, chunk: int = 256):
    """Full-sequence mamba1 mixer. x: [B,S,d] -> (y [B,S,d], (h, conv_state))."""
    s = cfg.ssm
    b, S, d = x.shape
    di = s.expand * d
    n = s.state_dim
    dtr = max(d // 16, 1)
    xz = x @ p["in_proj"]
    xi, z = xz[..., :di], xz[..., di:]
    xi, conv_state = _causal_conv(xi, p["conv_w"], p["conv_b"], conv0)

    proj = xi @ p["x_proj"]
    dt = jax.nn.softplus(proj[..., :dtr] @ p["dt_w"]
                         + p["dt_b"]).astype(jnp.float32)        # [B,S,di]
    Bm = proj[..., dtr:dtr + n].astype(jnp.float32)              # [B,S,N]
    Cm = proj[..., dtr + n:].astype(jnp.float32)                 # [B,S,N]
    A = -jnp.exp(p["A_log"])                                     # [di,N]

    if h0 is None:
        h0 = jnp.zeros((b, di, n), jnp.float32)
    nchunks = max(S // chunk, 1)
    chunk = S // nchunks

    def body(h, xs):
        dt_c, B_c, x_c, C_c = xs                                 # [B,c,...]
        x_c = x_c.astype(jnp.float32)        # converted per chunk, not hoisted
        a = jnp.exp(dt_c[..., None] * A)                         # [B,c,di,N]
        bx = (dt_c * x_c)[..., None] * B_c[:, :, None, :]        # [B,c,di,N]
        hs, h_last = _selective_scan_chunk(a, bx, h)
        y = jnp.einsum("bcdn,bcn->bcd", hs, C_c)
        return h_last, y

    def split(t):  # [B,S,...] -> [nchunks,B,c,...]
        return t.reshape(b, nchunks, chunk, *t.shape[2:]).swapaxes(0, 1)

    h_last, ys = jax.lax.scan(body, h0,
                              (split(dt), split(Bm), split(xi), split(Cm)))
    y = ys.swapaxes(0, 1).reshape(b, S, di)
    y = y + xi.astype(jnp.float32) * p["D"]
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"], (h_last, conv_state)


def mamba1_decode(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
                  h: jax.Array, conv_state: jax.Array):
    """x: [B,1,d]; single-step state update."""
    s = cfg.ssm
    b, _, d = x.shape
    di = s.expand * d
    n = s.state_dim
    dtr = max(d // 16, 1)
    xz = x @ p["in_proj"]
    xi, z = xz[..., :di], xz[..., di:]
    xi, conv_state = _causal_conv(xi, p["conv_w"], p["conv_b"], conv_state)
    proj = xi @ p["x_proj"]
    dt = jax.nn.softplus(proj[..., :dtr] @ p["dt_w"] + p["dt_b"]).astype(jnp.float32)
    Bm = proj[..., dtr:dtr + n].astype(jnp.float32)
    Cm = proj[..., dtr + n:].astype(jnp.float32)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt[:, 0, :, None] * A)                           # [B,di,N]
    bx = (dt[:, 0] * xi[:, 0].astype(jnp.float32))[..., None] * Bm[:, 0, None, :]
    h = a * h + bx
    y = jnp.einsum("bdn,bn->bd", h, Cm[:, 0])[:, None]
    y = y + xi.astype(jnp.float32) * p["D"]
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"], (h, conv_state)


# ---------------------------------------------------------------- mamba2 (SSD)

def mamba2_specs(cfg: ArchConfig, n_layers: int) -> Dict[str, ParamSpec]:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    nh = di // s.head_dim
    n = s.state_dim
    L = n_layers
    ax = ("layers",)
    # in_proj packs [z, x, B, C, dt]
    proj_out = 2 * di + 2 * n + nh
    return {
        "ln": ParamSpec((L, d), ax + ("embed",), init="ones", dtype="float32"),
        "in_proj": ParamSpec((L, d, proj_out), ax + ("embed", "ssm_inner")),
        "conv_w": ParamSpec((L, s.conv_width, di + 2 * n),
                            ax + ("conv", "ssm_inner")),
        "conv_b": ParamSpec((L, di + 2 * n), ax + ("ssm_inner",), init="zeros"),
        "A_log": ParamSpec((L, nh), ax + (None,), init="ssm_a", dtype="float32"),
        "dt_b": ParamSpec((L, nh), ax + (None,), init="ssm_dt", dtype="float32"),
        "D": ParamSpec((L, nh), ax + (None,), init="ones", dtype="float32"),
        "gate_ln": ParamSpec((L, di), ax + ("ssm_inner",), init="ones",
                             dtype="float32"),
        "out_proj": ParamSpec((L, di, d), ax + ("ssm_inner", "embed")),
    }


def _ssd_chunk_dual(xh, Bc, Cc, dtc, A, h0, chunk):
    """SSD chunked dual form.

    xh: [B,S,H,P]; Bc,Cc: [B,S,N]; dtc: [B,S,H] (softplus'd); A: [H] (negative).
    Returns y [B,S,H,P] and final state [B,H,P,N]. All float32.
    """
    b, S, H, P = xh.shape
    n = Bc.shape[-1]
    nchunks = max(S // chunk, 1)
    c = S // nchunks

    def split(t):
        return t.reshape(b, nchunks, c, *t.shape[2:]).swapaxes(0, 1)

    xs = (split(xh), split(Bc), split(Cc), split(dtc))

    def body(h, xs_c):
        x_c, B_c, C_c, dt_c = xs_c                               # [B,c,...]
        da = dt_c * A                                            # [B,c,H] (<=0)
        seg = jnp.cumsum(da, axis=1)                             # [B,c,H]
        # intra-chunk: scores[i,j] = C_i.B_j * exp(seg_i - seg_j), j <= i
        gap = seg[:, :, None, :] - seg[:, None, :, :]            # [B,c,c,H]
        mask = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.where(mask[None, :, :, None], jnp.exp(gap), 0.0)
        cb = jnp.einsum("bin,bjn->bij", C_c, B_c)                # [B,c,c]
        scores = cb[..., None] * decay                           # [B,c,c,H]
        xdt = x_c * dt_c[..., None]                              # [B,c,H,P]
        y = jnp.einsum("bijh,bjhp->bihp", scores, xdt)
        # inter-chunk: contribution of the carried state
        y = y + jnp.einsum("bin,bhpn,bih->bihp", C_c, h, jnp.exp(seg))
        # new carried state
        last = seg[:, -1:, :]                                    # [B,1,H]
        w = jnp.exp(last - seg)                                  # [B,c,H]
        h_new = (h * jnp.exp(last)[:, 0, :, None, None]
                 + jnp.einsum("bch,bchp,bcn->bhpn", w * dt_c, x_c, B_c))
        return h_new, y

    h_last, ys = jax.lax.scan(body, h0, xs)
    y = ys.swapaxes(0, 1).reshape(b, S, H, P)
    return y, h_last


def _mamba2_project(cfg, p, x, conv0):
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    n = s.state_dim
    nh = di // s.head_dim
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * n]
    dt = zxbcdt[..., di + di + 2 * n:]
    xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv0)
    xi = xbc[..., :di]
    Bc = xbc[..., di:di + n].astype(jnp.float32)
    Cc = xbc[..., di + n:].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_b"])     # [B,S,H]
    xh = xi.astype(jnp.float32).reshape(*xi.shape[:-1], nh, s.head_dim)
    return z, xi, xh, Bc, Cc, dt, conv_state


def _mamba2_out(cfg, p, y, xh, dt, z, x_dtype):
    y = y + xh * p["D"][:, None]                                 # D skip per head
    b, S = y.shape[:2]
    y = y.reshape(b, S, -1)
    # gated RMSNorm (mamba2 norm-before-out_proj)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    rms = jnp.sqrt(jnp.mean(y ** 2, axis=-1, keepdims=True) + 1e-5)
    y = (y / rms) * p["gate_ln"]
    return y.astype(x_dtype) @ p["out_proj"]


def mamba2_seq(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
               h0: Optional[jax.Array] = None, conv0=None, chunk: int = 256):
    s = cfg.ssm
    b, S, d = x.shape
    di = s.expand * d
    nh = di // s.head_dim
    z, xi, xh, Bc, Cc, dt, conv_state = _mamba2_project(cfg, p, x, conv0)
    A = -jnp.exp(p["A_log"])                                     # [H]
    if h0 is None:
        h0 = jnp.zeros((b, nh, s.head_dim, s.state_dim), jnp.float32)
    chunk = min(chunk, S)
    y, h_last = _ssd_chunk_dual(xh, Bc, Cc, dt, A, h0, chunk)
    return _mamba2_out(cfg, p, y, xh, dt, z, x.dtype), (h_last, conv_state)


def mamba2_decode(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
                  h: jax.Array, conv_state: jax.Array):
    s = cfg.ssm
    z, xi, xh, Bc, Cc, dt, conv_state = _mamba2_project(cfg, p, x, conv_state)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt[:, 0] * A)                                    # [B,H]
    dx = dt[:, 0, :, None] * xh[:, 0]                            # [B,H,P]
    # y = C.(a h + dx B) from the old state, so that the new state has one
    # consumer (the caller's in-place update) and is never staged whole
    y = (a[..., None] * jnp.einsum("bhpn,bn->bhp", h, Cc[:, 0])
         + dx * jnp.einsum("bn,bn->b", Bc[:, 0], Cc[:, 0])[:, None, None])
    h = a[..., None, None] * h + dx[..., None] * Bc[:, 0, None, None, :]
    return (_mamba2_out(cfg, p, y[:, None], xh, dt, z, x.dtype),
            (h, conv_state))
