"""Expert parallelism without its exchange: the layer that holds one
chip's share of the experts (``moe.held_experts``) routes over all of them
and drops nothing, so the parts that all the shares give, with the shared
expert counted once, add up to the whole layer. The serving path (ragged
matmuls over the stacked experts of every layer, ``layer`` given) and the
training path (every held expert on every token) give the same share."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import init_params, model_specs
from repro.models import moe as moe_lib

ROUTED, SHARES = 8, 4


def _cfg(n_experts, first):
    cfg = reduced(get_arch("granite-4.0-h-small"), dtype="float32")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=n_experts, n_routed=ROUTED, first_expert=first,
        top_k=3))


def _layer(cfg, key):
    """One layer's MoE parameters, the experts kept as a stack of one
    layer (the serving path's layout, ``layer=0``)."""
    p = init_params(model_specs(cfg), key, "float32")["x"]["layers"]
    return {k: a if k.startswith("we_") else a[0] for k, a in p.items()}


def share(cfg, p, x, layer=0):
    return moe_lib.held_experts(cfg, p, x, layer)


@pytest.mark.parametrize("tokens", [1, 5, 64])
def test_shares_sum_to_the_whole_layer(tokens):
    whole = _cfg(ROUTED, 0)
    p = _layer(whole, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, whole.d_model))
    want, sizes = share(whole, p, x)
    want = want + moe_lib.shared_expert(p, x)
    per = ROUTED // SHARES
    got = moe_lib.shared_expert(p, x)
    routed = []
    for s in range(SHARES):
        cfg = _cfg(per, s * per)
        held = dict(p, **{k: p[k][:, s * per:(s + 1) * per]
                          for k in ("we_gate", "we_up", "we_down")})
        out, n = share(cfg, held, x)
        got = got + out
        routed.append(n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(routed), np.asarray(sizes))
    assert int(sizes.sum()) == tokens * whole.moe.top_k


def test_dense_path_gives_the_same_share():
    """The training path's dense sum over the held experts equals the
    ragged one."""
    cfg = _cfg(3, 2)
    p = _layer(cfg, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, cfg.d_model))
    a, na = share(cfg, p, x)
    b, nb = share(cfg, {k: v[0] if k.startswith("we_") else v
                        for k, v in p.items()}, x, layer=None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(na), np.asarray(nb))


def test_no_token_is_dropped_under_uneven_routing():
    """Every token routed to one held expert: each token's gated expert
    output is there, as a dense reference computes it."""
    cfg = _cfg(2, 0)
    p = _layer(cfg, jax.random.PRNGKey(2))
    bias = jnp.zeros((cfg.d_model, ROUTED)).at[:, 1].set(10.0)
    p = dict(p, router=p["router"] + bias)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (32, cfg.d_model)))
    out, sizes = share(cfg, p, x)
    assert int(sizes[1]) == 32
    logits = x @ p["router"]
    top, ids = jax.lax.top_k(logits, cfg.moe.top_k)
    gates = jax.nn.softmax(top, -1)
    want = jnp.zeros_like(x)
    for e in range(2):
        w = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
        h = jax.nn.silu(x @ p["we_gate"][0, e]) * (x @ p["we_up"][0, e])
        want = want + w[:, None] * (h @ p["we_down"][0, e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
