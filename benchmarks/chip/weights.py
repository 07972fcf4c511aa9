"""Weights made from the seed, laid out from a configuration's numbers.

The layout is the one the system under test takes (``x``: backbone, ``y``:
head), written here from the configuration so that the benchmark makes the
weights and the reference reads the same arrays: ``check_layout`` compares
it with the program's own abstract parameters before a run starts.
Matrices are N(0, 0.02^2) in the configuration's dtype, norm weights ones
in float32. All of it is made on the device in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _attn(c, L, pre=""):
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return {pre + "ln_attn": ((L, d), "ones"),
            pre + "wq": ((L, d, h, hd), "normal"),
            pre + "wk": ((L, d, kv, hd), "normal"),
            pre + "wv": ((L, d, kv, hd), "normal"),
            pre + "wo": ((L, h, hd, d), "normal")}


def _mlp(c, L):
    d, f = c["d_model"], c["d_ff"]
    return {"ln_mlp": ((L, d), "ones"), "wi": ((L, d, f), "normal"),
            "wu": ((L, d, f), "normal"), "wd": ((L, f, d), "normal")}


def layout(c):
    """{path: (shape, init)} as a nested dict."""
    L = c["n_layers"]
    layers = {**_attn(c, L), **_mlp(c, L)}
    x = {"embed": ((c["vocab"], c["d_model"]), "normal"), "layers": layers}
    if c.get("encoder_layers"):
        layers.update(_attn(c, L, pre="c"))
        E = c["encoder_layers"]
        x["encoder"] = {"layers": {**_attn(c, E), **_mlp(c, E)},
                        "ln_out": ((c["d_model"],), "ones")}
    y = {"final_norm": ((c["d_model"],), "ones"),
         "head": ((c["d_model"], c["vocab"]), "normal")}
    return {"x": x, "y": y}


def _is_spec(t):
    return isinstance(t, tuple) and len(t) == 2 and isinstance(t[1], str)


def abstract(c):
    dt = jnp.dtype(c["dtype"])
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s[0], jnp.float32 if s[1] == "ones" else dt),
        layout(c), is_leaf=_is_spec)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec_items):
    out = []
    for i, (shape, init, dt) in enumerate(spec_items):
        if init == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            k = jax.random.fold_in(key, i)
            out.append((0.02 * jax.random.normal(k, shape, dt)).astype(dt))
    return out


def make(c, key):
    """The weights for seed-derived ``key``, on the default device."""
    leaves, tdef = jax.tree.flatten(layout(c), is_leaf=_is_spec)
    dt = jnp.dtype(c["dtype"]).name
    items = tuple((tuple(s), init, dt) for s, init in leaves)
    return jax.tree.unflatten(tdef, _make(key, items))


def check_layout(c, program_abstract):
    """Raise where the program's parameters differ from this layout."""
    want = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype).name),
                        abstract(c))
    got = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype).name),
                       program_abstract)
    if want != got:
        raise SystemExit(f"weights: the program's parameter layout differs "
                         f"from the configuration's: {got} != {want}")
