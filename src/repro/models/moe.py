"""Mixture-of-Experts layer: top-k token-choice routing with capacity-based
scatter dispatch (GShard-style semantics without the dense one-hot einsum),
and ``held_experts``, the drop-free layer of one chip's share of the experts
(expert parallelism without its exchange).

FLOPs are the honest active FLOPs (E x C x d x f); dispatch/combine are
scatter/gather. Experts are sharded over the ``model`` mesh axis (logical axis
``experts``); with fed_mode="zero" the expert FFN dim additionally shards over
``data`` (logical axis ``expert_mlp``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import ParamSpec


def moe_specs(cfg: ArchConfig, n_layers: int) -> Dict[str, ParamSpec]:
    e = cfg.moe
    d = cfg.d_model
    L = n_layers
    specs = {
        "router": ParamSpec((L, d, e.router_width), ("layers", "embed", None)),
        "we_gate": ParamSpec((L, e.n_experts, d, e.d_ff_expert),
                             ("layers", "experts", "embed", "expert_mlp")),
        "we_up": ParamSpec((L, e.n_experts, d, e.d_ff_expert),
                           ("layers", "experts", "embed", "expert_mlp")),
        "we_down": ParamSpec((L, e.n_experts, e.d_ff_expert, d),
                             ("layers", "experts", "expert_mlp", "embed")),
    }
    if e.d_ff_shared:
        specs.update({
            "ws_gate": ParamSpec((L, d, e.d_ff_shared), ("layers", "embed", "mlp")),
            "ws_up": ParamSpec((L, d, e.d_ff_shared), ("layers", "embed", "mlp")),
            "ws_down": ParamSpec((L, e.d_ff_shared, d), ("layers", "mlp", "embed")),
        })
    return specs


def _moe_group(cfg: ArchConfig, p: Dict[str, jax.Array], xf: jax.Array,
               capacity: int) -> jax.Array:
    """Dispatch/compute/combine for ONE group of tokens. xf: [n, d]."""
    e = cfg.moe
    n, d = xf.shape
    logits = jnp.einsum("nd,de->ne", xf, p["router"]).astype(jnp.float32)
    gates, eids = jax.lax.top_k(logits, e.top_k)                 # [n, k]
    gates = jax.nn.softmax(gates, axis=-1).astype(xf.dtype)

    flat_eids = eids.reshape(-1)                                 # [n*k]
    onehot = jax.nn.one_hot(flat_eids, e.n_experts, dtype=jnp.int32)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1)             # [n*k, E]
    slot = jnp.take_along_axis(pos_in_expert, flat_eids[:, None], axis=1)[:, 0]
    keep = slot < capacity
    slot = jnp.minimum(slot, capacity - 1)

    # dispatch: [E, C, d]
    src = jnp.repeat(xf, e.top_k, axis=0) * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((e.n_experts, capacity, d), xf.dtype)
    buf = buf.at[flat_eids, slot].add(src)

    h_g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"])
    h_u = jnp.einsum("ecd,edf->ecf", buf, p["we_up"])
    h = jax.nn.silu(h_g) * h_u
    # combine traffic crosses the expert (model) axis: keep it in xf.dtype
    out_buf = jnp.einsum("ecf,efd->ecd", h,
                         p["we_down"]).astype(xf.dtype)          # [E, C, d]

    # combine: gather each token's k slots
    gathered = out_buf[flat_eids, slot]                          # [n*k, d]
    gathered = gathered * (gates.reshape(-1)[:, None]
                           * keep[:, None].astype(xf.dtype))
    return gathered.reshape(n, e.top_k, d).sum(axis=1)


def apply_moe(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """x: [B, S, d] -> [B, S, d]. ``p`` holds one layer's (unstacked) params.

    GShard-style grouping: each batch row is its own dispatch group (vmapped),
    so all dispatch buffers carry the sharded batch dim — a single global
    group makes the one-hot/cumsum/scatter buffers scale with GLOBAL tokens
    and replicates them across the 512-chip mesh (measured: 74 GiB/device on
    the 2-pod MoE prefill)."""
    e = cfg.moe
    b, s, d = x.shape
    capacity = max(int(s * e.top_k * e.capacity_factor / e.n_experts), 4)
    out = jax.vmap(lambda row: _moe_group(cfg, p, row, capacity))(x)

    if e.d_ff_shared:
        hs = jax.nn.silu(x @ p["ws_gate"]) * (x @ p["ws_up"])
        out = out + hs @ p["ws_down"]
    return out


def held_experts(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
                 layer=None):
    """The part of a top-k MoE layer that this chip's experts give.

    x: [..., d]. The router scores all ``router_width`` experts; each token
    keeps its top k, with a softmax over those k as its gates. Only the
    experts held here (ids ``first_expert`` .. + ``n_experts``) are
    computed, with no capacity, so no token is dropped however unevenly
    the tokens route. The shared expert is not part of it
    (``shared_expert``): the shares of all chips sum to the routed part of
    the whole layer. Returns (out [..., d], tokens routed to each held
    expert [n_experts] int32).

    With ``layer`` (serving), ``p``'s expert weights are the whole stack
    [L, n_experts, ...] and ``layer`` the layer's index: the held
    (token, expert) pairs are sorted by expert and run as one ragged
    matmul per projection over the stack seen as L * n_experts groups, of
    which only this layer's have rows, so the experts are read in place (a
    slice of the stack would be copied out: a custom call takes no fused
    slice). Without it (training, which vmaps the layer over clients with
    their own experts, a batching ``ragged_dot`` does not have), every
    held expert runs on every token, weighted by the token's gate."""
    e = cfg.moe
    lead, d = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    logits = jnp.einsum("nd,de->ne", xf, p["router"],
                        preferred_element_type=jnp.float32)
    gates, eids = jax.lax.top_k(logits, e.top_k)                 # [n, k]
    gates = jax.nn.softmax(gates, axis=-1)
    local = eids.reshape(-1) - e.first_expert                    # [n*k]
    held = (local >= 0) & (local < e.n_experts)
    sizes = jnp.zeros((e.n_experts,), jnp.int32).at[
        jnp.where(held, local, e.n_experts)].add(1, mode="drop")
    if layer is None:
        w = jnp.sum(jnp.where(
            (eids - e.first_expert)[:, :, None] == jnp.arange(e.n_experts),
            gates[:, :, None], 0.0), axis=1)                     # [n, E]
        h = jax.nn.silu(jnp.einsum("nd,edf->enf", xf, p["we_gate"])) * \
            jnp.einsum("nd,edf->enf", xf, p["we_up"])
        out = jnp.einsum("enf,efd,ne->nd", h, p["we_down"],
                         w.astype(h.dtype))
        return out.astype(x.dtype).reshape(lead + (d,)), sizes
    # held pairs first, grouped by expert; a token holds at most
    # min(k, n_experts) of them, which bounds the rows
    order = jnp.argsort(jnp.where(held, local, e.n_experts), stable=True)
    m = n * min(e.top_k, e.n_experts)
    order = order[:m]
    tok = order // e.top_k
    keep = held[order]
    xs = jnp.take(xf, tok, axis=0)
    w = {k: p[k].reshape((-1,) + p[k].shape[2:])
         for k in ("we_gate", "we_up", "we_down")}
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((w["we_gate"].shape[0],), jnp.int32), sizes,
        (layer * e.n_experts,))
    g = jax.lax.ragged_dot(xs, w["we_gate"], groups)
    u = jax.lax.ragged_dot(xs, w["we_up"], groups)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    o = jax.lax.ragged_dot(h, w["we_down"], groups,
                           preferred_element_type=jnp.float32)
    # rows past the held pairs are not computed: mask them, not scale them
    o = jnp.where(keep[:, None], o * gates.reshape(-1)[order][:, None], 0.0)
    out = jnp.zeros((n, d), jnp.float32).at[tok].add(o)
    return out.astype(x.dtype).reshape(lead + (d,)), sizes


def shared_expert(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """The always-on SwiGLU beside the routed experts."""
    hs = jax.nn.silu(x @ p["ws_gate"]) * (x @ p["ws_up"])
    return hs @ p["ws_down"]


def aux_load_balance_loss(logits: jax.Array, eids: jax.Array, n_experts: int):
    """Switch-style load-balance auxiliary loss (returned for monitoring)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=0)
    ce = jnp.bincount(eids.reshape(-1), length=n_experts) / eids.size
    return n_experts * jnp.sum(me * ce)
