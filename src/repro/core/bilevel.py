"""Bilevel problem abstraction (Problem (1) of the paper).

A ``BilevelProblem`` bundles the per-client UL objective ``f^m(x, y; xi)`` and
LL objective ``g^m(x, y; zeta)``. Two calling conventions:

- generic: ``f(xp, yp, batch)`` / ``g(xp, yp, batch)`` scalars — used by the
  paper-faithful hypergradient estimator.
- factored (optional fast path): ``features(xp, batch)`` with
  ``g_from_feats(yp, feats, batch)`` / ``f_from_feats(yp, feats, batch)``.
  When the LL variable only touches the loss *through* the features (true for
  the hyper-representation split: y = head), Neumann ``∇²yy g`` products need
  only head-local autodiff against cached features — mathematically identical,
  far cheaper (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.tree_util import tree_sqnorm


@dataclasses.dataclass(frozen=True)
class BilevelProblem:
    f: Callable[..., jax.Array]                 # f(xp, yp, batch) -> scalar
    g: Callable[..., jax.Array]                 # g(xp, yp, batch) -> scalar
    features: Optional[Callable[..., Any]] = None       # features(xp, batch)
    f_from_feats: Optional[Callable[..., jax.Array]] = None
    g_from_feats: Optional[Callable[..., jax.Array]] = None
    # optional memory-bounded gradient paths (microbatched accumulation):
    grad_f_xy: Optional[Callable[..., Any]] = None  # (xp,yp,b) -> (gx, gy)
    grad_g_y: Optional[Callable[..., Any]] = None   # (xp,yp,b) -> gy
    # optional sharding re-assertion for x-/y-space gradient trees
    constrain_x: Optional[Callable[..., Any]] = None
    constrain_y: Optional[Callable[..., Any]] = None

    @property
    def factored(self) -> bool:
        return self.features is not None


def _split_chunks(batch, nc: int):
    return jax.tree.map(
        lambda a: a.reshape((nc, a.shape[0] // nc) + a.shape[1:]), batch)


def microbatched_grad(loss, argnums, nc: int, constrain=None,
                      acc_dtype=None):
    """grad of a mean-loss, accumulated over ``nc`` microbatches via lax.scan.

    Bounds backward transients/residuals to one microbatch. ``acc_dtype``
    None = accumulate in f32 (precise); "param" = accumulate in each param's
    own dtype (bf16 at LLM scale — halves accumulator + fused-dot buffers;
    the CPU-scale paper experiments use f32 params either way).
    ``constrain`` (optional) re-applies the param sharding to the accumulator
    so GSPMD doesn't replicate it.
    """
    gfn = jax.grad(loss, argnums=argnums)

    def _constrain(tree, like):
        return tree if constrain is None else constrain(tree)

    def wrapped(xp, yp, batch):
        chunks = _split_chunks(batch, nc)
        args = (xp, yp)
        like = args[argnums] if isinstance(argnums, int) else tuple(
            args[i] for i in argnums)
        dt = (lambda p: p.dtype) if acc_dtype == "param" else (
            lambda p: jnp.float32)
        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, dt(p)), like)

        def body(acc, chunk):
            g = _constrain(gfn(xp, yp, chunk), like)
            acc = jax.tree.map(lambda a, gi: a + (gi / nc).astype(a.dtype),
                               acc, g)
            return _constrain(acc, like), None

        acc, _ = jax.lax.scan(body, acc0, chunks)
        return jax.tree.map(lambda a, p: a.astype(p.dtype), acc, like)

    return wrapped


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy. logits [..., V], labels [...] int."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    # one-hot select instead of take_along_axis: partitions cleanly when the
    # vocab dim is sharded (gather would force an all-gather of the logits).
    # 1-D arange (not a broadcasted iota) so the comparison fuses instead of
    # materializing an s32 [B,S,V] tensor.
    iota = jnp.arange(lf.shape[-1], dtype=labels.dtype)
    ll = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), axis=-1)
    loss = lse - ll
    if mask is not None:
        return (loss * mask).sum() / jnp.maximum(mask.sum(), 1)
    return loss.mean()


def lm_bilevel_problem(cfg, ctx, nu: float,
                       microbatch: Optional[int] = None) -> BilevelProblem:
    """Hyper-representation learning on the LM: x = backbone, y = head.

    ``batch`` keys: "tokens" (LL/UL chosen by caller), optional modality stubs.
    LL adds the strongly-convex regulariser (nu/2)||y||^2 (Problem (3)).
    ``microbatch``: max sequences per gradient microbatch (memory bound for the
    big-batch ∇(x,y) f and ∇y g paths).
    """
    from repro.models.model import features as model_features
    from repro.models.model import head_logits

    def feats_fn(xp, batch):
        return model_features(cfg, xp, batch, ctx)

    def _xent_head(yp, feats, batch):
        logits = head_logits(cfg, yp, feats[:, :-1])
        return softmax_xent(logits, batch["tokens"][:, 1:])

    def g_from_feats(yp, feats, batch):
        reg = 0.5 * nu * tree_sqnorm(yp)
        return _xent_head(yp, feats, batch) + reg

    def f_from_feats(yp, feats, batch):
        return _xent_head(yp, feats, batch)

    def g(xp, yp, batch):
        return g_from_feats(yp, feats_fn(xp, batch), batch)

    def f(xp, yp, batch):
        return f_from_feats(yp, feats_fn(xp, batch), batch)

    if cfg.tie_embeddings:
        # the head is x's embedding table, so the LL loss reaches x through
        # more than the features: no factored path
        def _xent_tied(xp, yp, batch):
            logits = head_logits(cfg, yp, feats_fn(xp, batch)[:, :-1],
                                 xp["embed"])
            return softmax_xent(logits, batch["tokens"][:, 1:])

        def g(xp, yp, batch):
            return _xent_tied(xp, yp, batch) + 0.5 * nu * tree_sqnorm(yp)

        def f(xp, yp, batch):
            return _xent_tied(xp, yp, batch)

    def _nc(batch):
        n = batch["tokens"].shape[0]
        if microbatch is None or n <= microbatch:
            return 1
        assert n % microbatch == 0, (n, microbatch)
        return n // microbatch

    # re-assert the param sharding on grad accumulators (GSPMD otherwise tends
    # to replicate the f32 accumulators of weight grads)
    from repro.models.model import model_specs
    from repro.models.params import axes_tree
    from repro.sharding import shard_act
    _axes = axes_tree(model_specs(cfg))

    def _is_axes(t):
        return isinstance(t, tuple) and all(u is None or isinstance(u, str)
                                            for u in t)

    def _constrain_like(axes):
        def fn(tree):
            return jax.tree.map(lambda g, a: shard_act(g, a, ctx.rules,
                                    fallback=("model",)),
                                tree, axes, is_leaf=lambda t: _is_axes(t))
        return fn

    acc_dtype = "param" if cfg.dtype == "bfloat16" else None

    def grad_f_xy(xp, yp, batch):
        c = _constrain_like((_axes["x"], _axes["y"]))
        return microbatched_grad(f, (0, 1), _nc(batch), c,
                                 acc_dtype)(xp, yp, batch)

    def grad_g_y(xp, yp, batch):
        c = _constrain_like(_axes["y"])
        return microbatched_grad(g, 1, _nc(batch), c, acc_dtype)(xp, yp, batch)

    factored = {} if cfg.tie_embeddings else dict(
        features=feats_fn, f_from_feats=f_from_feats,
        g_from_feats=g_from_feats)
    return BilevelProblem(f=f, g=g, **factored,
                          grad_f_xy=grad_f_xy, grad_g_y=grad_g_y,
                          constrain_x=_constrain_like(_axes["x"]),
                          constrain_y=_constrain_like(_axes["y"]))


def quadratic_bilevel_problem(H: jax.Array, Bm: jax.Array, c: jax.Array,
                              Q: jax.Array) -> BilevelProblem:
    """Analytic test problem with closed-form hypergradient:

      g(x, y) = 1/2 y^T H y - (B x)^T y          (H ≻ 0)
      f(x, y) = 1/2 ||y - c||^2 + 1/2 x^T Q x
      y*(x)   = H^{-1} B x
      ∇F(x)   = Q x + B^T H^{-1} (y*(x) - c)
    """
    def g(xp, yp, batch):
        del batch
        return 0.5 * yp @ H @ yp - (Bm @ xp) @ yp

    def f(xp, yp, batch):
        del batch
        return 0.5 * jnp.sum((yp - c) ** 2) + 0.5 * xp @ Q @ xp

    return BilevelProblem(f=f, g=g)


def quadratic_true_grad(H, Bm, c, Q, x):
    y_star = jnp.linalg.solve(H, Bm @ x)
    return Q @ x + Bm.T @ jnp.linalg.solve(H, y_star - c)
