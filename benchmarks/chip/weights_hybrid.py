"""Weights made from the seed for the hybrid (Mamba-2 + attention + MoE)
configuration, laid out from its numbers.

The layout is the one the system under test takes for its ``hybrid_moe``
family: ``x`` holds the embedding (tied: it is also the head), ``layers``
(every layer's MoE block: router over all routed experts, the held
experts, the shared expert and their norm), ``mamba`` (the Mamba-2
mixers) and ``attn`` (the attention mixers); ``y`` holds the final norm.
``weights.check_layout`` compares it with the program's own abstract
parameters before a run starts. Matrices are N(0, 0.02^2) in the
configuration's dtype and norm weights ones in float32, as in
``weights.py``; the mixers' own parameters are those the published model
is initialised with (``assumed`` in the configuration file). The
embedding table is N(0, EMBED_STD^2): it is also the head, and the
published embedding multiplier (12) scales it into the residual stream,
so at 0.02 every position's own token would lead the logits by several
times the best other logit whatever the context, each served token would
repeat the last, and no comparison of served tokens could tell a sound
decode from one that ignores its cache. All of it is made on the device
in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import harness

# init -> dtype: "normal", "embed" and "uniform" take the configuration's
F32_INITS = ("ones", "a_log", "dt_bias")
EMBED_STD = 0.002


def sizes(c):
    """The numbers the layout and the counts use, from the catalog keys."""
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    n = c["mamba_n_groups"] * c["mamba_d_state"]
    types = c["layer_types"]
    return {"d": d, "di": di, "n": n, "nh": c["mamba_n_heads"],
            "p": c["mamba_d_head"], "conv_ch": di + 2 * n,
            "cw": c["mamba_d_conv"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"],
            "hd": d // c["num_attention_heads"],
            "e": c["num_local_experts"], "routed": c["published"][
                "num_local_experts"], "f": c["intermediate_size"],
            "fs": c["shared_intermediate_size"], "vocab": c["vocab_size"],
            "L": len(types), "Lm": types.count("mamba"),
            "La": types.count("attention")}


def layout(c):
    """{path: (shape, init)} as a nested dict."""
    s = sizes(c)
    d, L, Lm, La = s["d"], s["L"], s["Lm"], s["La"]
    layers = {
        "router": ((L, d, s["routed"]), "normal"),
        "we_gate": ((L, s["e"], d, s["f"]), "normal"),
        "we_up": ((L, s["e"], d, s["f"]), "normal"),
        "we_down": ((L, s["e"], s["f"], d), "normal"),
        "ws_gate": ((L, d, s["fs"]), "normal"),
        "ws_up": ((L, d, s["fs"]), "normal"),
        "ws_down": ((L, s["fs"], d), "normal"),
        "ln_mlp": ((L, d), "ones")}
    mamba = {
        "ln": ((Lm, d), "ones"),
        "in_proj": ((Lm, d, 2 * s["di"] + 2 * s["n"] + s["nh"]), "normal"),
        "conv_w": ((Lm, s["cw"], s["conv_ch"]), "uniform"),
        "conv_b": ((Lm, s["conv_ch"]), "uniform"),
        "A_log": ((Lm, s["nh"]), "a_log"),
        "dt_b": ((Lm, s["nh"]), "dt_bias"),
        "D": ((Lm, s["nh"]), "ones"),
        "gate_ln": ((Lm, s["di"]), "ones"),
        "out_proj": ((Lm, s["di"], d), "normal")}
    attn = {
        "ln_attn": ((La, d), "ones"),
        "wq": ((La, d, s["h"], s["hd"]), "normal"),
        "wk": ((La, d, s["kv"], s["hd"]), "normal"),
        "wv": ((La, d, s["kv"], s["hd"]), "normal"),
        "wo": ((La, s["h"], s["hd"], d), "normal")}
    return {"x": {"embed": ((s["vocab"], d), "embed"), "layers": layers,
                  "mamba": mamba, "attn": attn},
            "y": {"final_norm": ((d,), "ones")}}


def _is_spec(t):
    return isinstance(t, tuple) and len(t) == 2 and isinstance(t[1], str)


def abstract(c):
    dt = jnp.dtype(c["dtype"])
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s[0], jnp.float32 if s[1] in F32_INITS else dt),
        layout(c), is_leaf=_is_spec)


def check_layout(c, program_abstract):
    """Raise where the program's parameters differ from this layout
    (``weights.check_layout``'s comparison, on this layout)."""
    w = harness.load_module(harness.HERE / "weights.py")
    w.abstract = abstract
    w.check_layout(c, program_abstract)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec_items):
    out = []
    for i, (shape, init, dt) in enumerate(spec_items):
        k = jax.random.fold_in(key, i)
        if init == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif init in ("normal", "embed"):
            std = EMBED_STD if init == "embed" else 0.02
            out.append((std * jax.random.normal(k, shape, dt)).astype(dt))
        elif init == "uniform":             # Conv1d's default at fan-in 4
            out.append(jax.random.uniform(k, shape, dt, -0.5, 0.5))
        elif init == "a_log":               # A = -(1 .. heads)
            a = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
            out.append(jnp.broadcast_to(jnp.log(a), shape))
        else:                               # dt_bias: softplus^-1(dt)
            lo, hi = np.log(1e-3), np.log(1e-1)
            t = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
            out.append(t + jnp.log(-jnp.expm1(-t)))
    return out


def make(c, key):
    """The weights for seed-derived ``key``, on the default device."""
    leaves, tdef = jax.tree.flatten(layout(c), is_leaf=_is_spec)
    dt = jnp.dtype(c["dtype"]).name
    items = tuple((tuple(s), init, dt) for s, init in leaves)
    return jax.tree.unflatten(tdef, _make(key, items))
