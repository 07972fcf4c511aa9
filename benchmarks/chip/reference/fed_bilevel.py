"""Plain float32 reference of synchronous federated AdaFBiO rounds over a
client population, on the encoder-decoder LM of ``reference/model.py``.

The bilevel split is hyper-representation learning: the backbone is the
upper-level variable x, the head (final norm and output matrix) the
lower-level variable y. Per client and batch b:

    f(x, y; b) = next-token cross-entropy
    g(x, y; b) = f(x, y; b) + nu/2 |y|^2

The hypergradient estimate (AdaFBiO, arXiv:2211.01122, Eq. 15) with Neumann
depth k drawn from U{0..K-1}:

    p = K theta prod_{i<k} (I - theta H_i) grad_y f(x, y; xi)
    w = grad_x f(x, y; xi) - grad_x <grad_y g(x, y; zeta_0), p>

where H_i is the Hessian in y of g on the i-th Neumann batch. A local step
at iteration t, with eta_t = eta_k n^(1/3) / (eta_n + t)^(1/3) and
alpha = beta = min(c eta_t^2, 1):

    x' = x - lr_x eta_t w / (sqrt(a) + rho)
    y' = y - lr_y eta_t v / (b + rho)
    v' = grad_y g(x', y'; zeta) + (1 - alpha)(v - grad_y g(x, y; zeta))
    w' = w(x', y') + (1 - beta)(w - w(x, y))      (same batches, same k)

A round gathers the cohort's states, runs q local steps on each, sends each
client's change since the gather through stochastic 8-bit quantisation with
one scale per tensor (its own rounding noise, not the program's), averages
the reconstructions, and then the server refreshes
``a = varrho a + (1 - varrho) w_bar^2`` and
``b = min(varrho b + (1 - varrho) |v_bar|, 1e3)``, takes one more step from
the average as above, and hands that state to every client.

The initial estimators of each client are ``v = grad_y g`` on its first LL
batch and ``w`` as above; the server starts from ``a = w_bar^2`` and
``b = |v_bar|`` over the whole population. The Neumann depth of each draw
follows the key stream the configuration fixes (``depth_key``); that is the
only random choice that is shared with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import model as M

F32 = jnp.float32


def tmap(f, *ts):
    return jax.tree.map(f, *ts)


def tnorm(t):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(F32)))
                        for a in jax.tree.leaves(t)))


def xent(c, xp, yp, b):
    return M.next_token_xent(c, yp, M.features(c, xp, b["tokens"],
                                               b.get("frames")), b["tokens"])


def make_fns(c, fed):
    nu, K, theta = fed["nu"], fed["neumann_k"], fed["theta"]

    def f(x, y, b):
        return xent(c, x, y, b)

    def g(x, y, b):
        return xent(c, x, y, b) + 0.5 * nu * tnorm(y) ** 2

    gy_g = jax.grad(g, argnums=1)

    def hypergrad(x, y, bs, k):
        gx, gy = jax.grad(f, argnums=(0, 1))(x, y, bs["f"])
        p = gy
        for i in range(K - 1):
            bi = tmap(lambda a: a[i], bs["gi"])
            hv = jax.jvp(lambda yy: gy_g(x, yy, bi), (y,), (p,))[1]
            p = tmap(lambda pp, hh: jnp.where(i < k, pp - theta * hh, pp),
                     p, hv)
        p = tmap(lambda pp: K * theta * pp, p)

        def inner(xx):
            gyy = gy_g(xx, y, bs["g0"])
            return sum(jnp.vdot(u, w) for u, w in
                       zip(jax.tree.leaves(gyy), jax.tree.leaves(p)))
        corr = jax.grad(inner)(x)
        return tmap(jnp.subtract, gx, corr)

    return f, g, gy_g, hypergrad


def depth(key, K):
    return jax.random.randint(key, (), 0, K)


def eta_t(fed, t, n):
    return fed["eta_k"] * n ** (1 / 3) / (fed["eta_n"] + t) ** (1 / 3)


def quantize(key, x, bits=8):
    """Stochastic uniform quantisation of one tensor, one scale."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / qmax
    u = jax.random.uniform(key, x.shape)
    return jnp.clip(jnp.floor(x / scale + u), -qmax, qmax) * scale


class FedReference:
    """Runs the rounds one client at a time, so that only one client's
    state, the round's starting state and the running mean are held;
    every heavy function is jitted once."""

    def __init__(self, c, fed, n):
        self.c, self.fed, self.n = c, fed, n
        f, g, gy_g, hg = make_fns(c, fed)
        self.K = fed["neumann_k"]

        def estimators(x, y, bs, k):
            """(grad_y g on zeta, the hypergradient estimate) at (x, y)."""
            return gy_g(x, y, bs["g"]), hg(x, y, bs, k)
        self._est = jax.jit(estimators)

        def move(st, a, b, t):
            fd = self.fed
            eta = eta_t(fd, t, self.n)
            x = tmap(lambda p, w, aa: p - fd["lr_x"] * eta * w /
                     (jnp.sqrt(aa) + fd["rho"]), st["x"], st["w"], a)
            y = tmap(lambda p, v: p - fd["lr_y"] * eta * v / (b + fd["rho"]),
                     st["y"], st["v"])
            return x, y
        self._move = jax.jit(move)

        def storm(new, est, old, t):
            """new + (1 - alpha)(est - old), alpha = min(c eta^2, 1)."""
            fd = self.fed
            eta = eta_t(fd, t, self.n)
            al = jnp.minimum(fd["alpha_c1"] * eta ** 2, 1.0)
            be = jnp.minimum(fd["beta_c2"] * eta ** 2, 1.0)
            v = tmap(lambda n_, e, o: n_ + (1 - al) * (e - o), new[0],
                     est[0], old[0])
            w = tmap(lambda n_, e, o: n_ + (1 - be) * (e - o), new[1],
                     est[1], old[1])
            return v, w
        self._storm = jax.jit(storm)

        def add_message(acc, key, ref, cur, weight):
            """acc + weight * (ref + Q(cur - ref))."""
            leaves, tdef = jax.tree.flatten(
                tmap(lambda u, r: u - r, cur, ref))
            keys = jax.random.split(key, len(leaves))
            sent = jax.tree.unflatten(
                tdef, [quantize(kk, l) for kk, l in zip(keys, leaves)])
            return tmap(lambda s_, r, q_: s_ + weight * (r + q_), acc, ref,
                        sent)
        self._add = jax.jit(add_message)

        def sync(avg, a, b, t):
            fd = self.fed
            vr = fd["varrho"]
            a = tmap(lambda aa, w: vr * aa + (1 - vr) * w ** 2, a, avg["w"])
            b = jnp.minimum(vr * b + (1 - vr) * tnorm(avg["v"]), 1e3)
            eta = eta_t(fd, t, self.n)
            x = tmap(lambda p, w, aa: p - fd["lr_x"] * eta * w /
                     (jnp.sqrt(aa) + fd["rho"]), avg["x"], avg["w"], a)
            y = tmap(lambda p, v: p - fd["lr_y"] * eta * v / (b + fd["rho"]),
                     avg["y"], avg["v"])
            return {"x": x, "y": y, "v": avg["v"], "w": avg["w"]}, a, b
        self._sync = jax.jit(sync)
        self._loss = jax.jit(f)

    def step(self, st, bs, key, t, with_old):
        """One local step of one client at iteration t."""
        k = depth(jax.random.split(key)[0], self.K)
        tt = jnp.float32(t)
        x, y = self._move(st, self.a, self.b, tt)
        v, w = self._est(x, y, bs, k)
        if with_old:
            old = self._est(st["x"], st["y"], bs, k)
            v, w = self._storm((v, w), (st["v"], st["w"]), old, tt)
        return {"x": x, "y": y, "v": v, "w": w}

    def init(self, params, batches_n, keys):
        """Every client's initial estimators, and the server's matrices
        from their means over the whole population."""
        x = tmap(lambda p: p.astype(F32), params["x"])
        y = tmap(lambda p: p.astype(F32), params["y"])
        self.x0, self.y0, self.est = x, y, []
        for i in range(self.n):
            b = tmap(lambda a: a[i], batches_n)
            self.est.append(self._est(x, y, b, depth(keys[i], self.K)))
        mean = lambda j: tmap(lambda *u: sum(u) / self.n,
                              *[e[j] for e in self.est])
        v_bar, w_bar = mean(0), mean(1)
        self.a = tmap(lambda u: u ** 2, w_bar)
        self.b = tnorm(v_bar)
        self.state, self.t = None, 0

    def round(self, ids, batches_q, dither_key, depth_key):
        """One round for cohort ``ids`` with per-step batches
        ``batches_q`` (leading [q, C]); ``depth_key(gid, t)`` is the key
        whose first split draws the Neumann depth of client gid's step t.
        Returns the state every client holds afterwards."""
        fd, c = self.fed, len(ids)
        q = jax.tree.leaves(batches_q)[0].shape[0]
        withs = []
        for j in range(q):
            eta = float(eta_t(fd, self.t + j, self.n))
            withs.append(min(fd["alpha_c1"] * eta ** 2, 1.0) < 1.0 or
                         min(fd["beta_c2"] * eta ** 2, 1.0) < 1.0)
        acc = None
        for i, gid in enumerate(ids):
            if self.state is None:      # round 0: shared weights, own
                v, w = self.est[int(gid)]           # initial estimators
                ref = {"x": self.x0, "y": self.y0, "v": v, "w": w}
            else:
                ref = self.state
            cur = ref
            for j in range(q):
                cur = self.step(cur, tmap(lambda a: a[j, i], batches_q),
                                depth_key(int(gid), self.t + j),
                                self.t + j, withs[j])
            if acc is None:
                acc = tmap(jnp.zeros_like, cur)
            acc = self._add(acc, jax.random.fold_in(dither_key, int(gid)),
                            ref, cur, 1.0 / c)
            del cur
        self.t += q
        self.state, self.a, self.b = self._sync(acc, self.a, self.b,
                                                jnp.float32(self.t))
        self.t += 1
        return self.state

    def release(self):
        """Drop the arrays of the last run; the compiled programs stay."""
        self.x0 = self.y0 = self.est = self.state = self.a = self.b = None

    def loss(self, x, y, batch):
        return float(self._loss(tmap(lambda p: p.astype(F32), x),
                                tmap(lambda p: p.astype(F32), y), batch))
