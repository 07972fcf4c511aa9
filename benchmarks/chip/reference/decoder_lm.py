"""Plain float32 logits of a decoder-only LM over whole sequences, layer by
layer, for checking served tokens.

``served_gaps`` runs each prompt with its served tokens appended once
through ``reference/model.py``'s blocks and returns, for every served
token, how far its logit lies below the reference's best logit at that
position, in units of the spread of the reference's logits there. Weights are the benchmark's own (held in the served dtype); each
layer is cast to float32 only while it runs, and the head runs in vocab
blocks, so that a model that fills most of the chip still fits beside it.

``lower`` selects the control: every matmul operand rounded to float8
(e4m3, one scale per tensor) before a float32 matmul. It returns, instead
of the served tokens' gaps, the gaps of the tokens the lower precision
ranks first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import model as M

F32 = jnp.float32
VOCAB_BLOCK = 16384


def fp8(x):
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _cast(p, lower):
    return jax.tree.map((lambda a: fp8(a)) if lower else
                        (lambda a: a.astype(F32)), p)


PAD = 512


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(c, stack, i, h, lower):
    c = dict(c)
    p = _cast({k: a[i] for k, a in stack.items()}, lower)
    if lower:
        h = fp8(h)
    h = M.attn_block(c, p, h)
    if lower:
        h = fp8(h)
    return M.mlp_block(c, p, h)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head_block(c, yp, feats, lower):
    c = dict(c)
    hn = M.rmsnorm(feats, yp["final_norm"], c["norm_eps"])
    w = yp["head"]
    if lower:
        hn, w = fp8(hn), fp8(w)
    return M.ein("bsd,dv->bsv", hn, w)


def logits_at(c, params, tokens, rows, lower=False):
    """float32 logits [len(rows), vocab] of sequence ``tokens`` at
    positions ``rows``."""
    ct = tuple(sorted(c.items()))
    xp, yp = params["x"], params["y"]
    # causal attention: padding after the sequence leaves the positions
    # before it alone, and a few padded lengths compile once each
    n = len(tokens)
    toks = np.zeros(-(-n // PAD) * PAD, np.int32)
    toks[:n] = tokens
    h = jnp.take(xp["embed"], jnp.asarray(toks)[None], axis=0).astype(F32)
    for i in range(c["n_layers"]):
        h = _layer(ct, xp["layers"], jnp.int32(i), h, lower)
    # the rows padded to a fixed count, so the head compiles once
    idx = np.zeros(-(-len(rows) // PAD) * PAD, np.int32)
    idx[:len(rows)] = rows
    feats = h[:, jnp.asarray(idx)]
    v = c["vocab"]
    blocks = [_head_block(ct, {"final_norm": yp["final_norm"],
                               "head": yp["head"][:, s:s + VOCAB_BLOCK]},
                          feats, lower)
              for s in range(0, v, VOCAB_BLOCK)]
    return jnp.concatenate(blocks, -1)[0, :len(rows)]


def served_gaps(c, params, prompt, served, lower=False):
    """Gaps at each served position: the float32 reference's best logit
    minus its logit of the chosen token, in units of the standard deviation
    of the reference's logits there (so that a gap reads alike at any
    width). ``lower``: the chosen token is the control's first choice
    instead of the served one."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits_at(c, params, seq, rows)
    if lower:
        chosen = jnp.argmax(logits_at(c, params, seq, rows, lower=True), -1)
    else:
        chosen = jnp.asarray(served)
    got = jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return np.asarray((jnp.max(ref, -1) - got) / jnp.std(ref, -1))
