"""Driver for training cells: back-to-back synchronous population rounds.

Set-up builds one round program (the launcher's
``population_round_program``) with its bank, makes the weights and every
batch on the device from the seed, and drives rounds 0-2 through the same
call the window uses. Those three rounds are the ones the reference
follows. The window then runs rounds until ``--seconds`` have passed, each
round on fresh batches and a fresh cohort, and blocks on each round's bank
as the launcher does.

What is compared (each number beside its limit, from ``limits/<cell>.json``):

- ``loss_gap``: over rounds 0-2, the largest relative gap between the
  next-token loss of the program's state and of the reference's state, both
  evaluated by the reference on one held-out batch.
- ``grad_gap``: after round 0, the worst leaf of the estimators v and w the
  optimizer steps with: the gap between the norm of the program's leaf and
  the reference's, over the larger of the reference leaf's norm and the
  median leaf norm.
- ``change_gap``: the same measure of the parameters' change over rounds
  0-2 (x and y), leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import counts
import harness
import tracing
import weights as W

SALT_WEIGHTS, SALT_DATA, SALT_COHORT, SALT_RUN, SALT_DITHER = 1, 2, 3, 4, 5
CHECK_ROUNDS = 3
TRACE_SECONDS = 4.0
# batch indices past any round the window reaches
INIT_BATCH, HELD_BATCH = 1 << 30, (1 << 30) + 1


def program(conf, traffic):
    """(arch config, fed config, trainer, batch specs, batch axes) of the
    program."""
    from repro.configs import FedConfig
    from repro.configs.base import ShapeConfig
    from repro.fed.runtime import FederatedTrainer, client_batch_specs
    arch = harness.arch_config(conf)
    fed = FedConfig(**conf["fed"], q=traffic["q"])
    shape = ShapeConfig("bench", traffic["seq"], traffic["cohort"], "train")
    tr = FederatedTrainer(arch, fed, shape)
    specs, axes = client_batch_specs(arch, shape, traffic["cohort"], fed)
    # frame embeddings enter the residual stream: the model's dtype
    specs = {k: (s if s.dtype == jnp.int32 else
                 jax.ShapeDtypeStruct(s.shape, jnp.dtype(arch.dtype)))
             for k, s in specs.items()}
    return arch, fed, tr, specs, axes


def make_batch_fn(specs, vocab, frames_scale, lead):
    """Jitted ``fn(key) -> batch`` with leading dims ``lead``: uniform
    tokens, N(0, frames_scale^2) frame embeddings, every row distinct."""
    names = sorted(specs)

    def fn(key):
        out = {}
        for i, k in enumerate(names):
            s = specs[k]
            shape = tuple(lead) + tuple(s.shape[1:])
            kk = jax.random.fold_in(key, i)
            if s.dtype == jnp.int32:
                out[k] = jax.random.randint(kk, shape, 0, vocab, jnp.int32)
            else:
                out[k] = (frames_scale * jax.random.normal(
                    kk, shape, jnp.float32)).astype(s.dtype)
        return out
    return jax.jit(fn)


def tokens_per_round(specs, q):
    """Sequence positions one round consumes: decoder tokens and encoder
    frames of every batch, over the cohort and the q steps."""
    n = 0
    for k, s in specs.items():
        if k.endswith("tokens"):
            n += int(np.prod(s.shape))
        elif k.endswith("enc_embeds"):
            n += int(np.prod(s.shape[:-1]))
    return n * q


def init_fn(tr, n):
    """The population's initial states for given weights: each client's
    estimators from its own first batch, the server warmed on their mean
    (as the launcher's ``init_population_states`` does for its own
    weights)."""
    from repro.core.adafbio import warm_adaptive
    from repro.core.tree_util import tree_mean_axis0
    from repro.fed.runtime import split_client_batch

    def init(params, batch_n, keys):
        def one(k, b):
            return tr.alg.init_client_state(
                params["x"], params["y"], split_client_batch(tr.cfg, b), k)
        bank = jax.vmap(one)(keys, batch_n)
        server = tr.alg.init_server_state(
            jax.tree.map(lambda a: a[0], bank["x"]))
        server = warm_adaptive(server, tree_mean_axis0(bank), tr.fed)
        return bank, jnp.zeros((n,), jnp.int32), server
    return jax.jit(init)


def leaf_norms(tree):
    return [float(v) for v in jax.device_get(jax.tree.leaves(jax.jit(
        lambda t: jax.tree.map(lambda a: jnp.linalg.norm(
            a.astype(jnp.float32).reshape(-1)), t))(tree)))]


def worst_gap(prog, ref, keep=None):
    """max over leaves |prog - ref| / max(ref, median(ref))."""
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    med = float(np.median(ref))
    idx = range(len(ref)) if keep is None else keep
    gaps = [abs(prog[i] - ref[i]) / max(ref[i], med, 1e-30) for i in idx]
    return max(gaps) if gaps else float("nan")


def ref_batches(b):
    """Program batch dict (leading client axis) -> the reference's
    {g, f, g0, gi} per-client batches."""
    def pack(pre):
        d = {"tokens": b[pre + "tokens"]}
        if pre + "enc_embeds" in b:
            d["frames"] = b[pre + "enc_embeds"]
        return d
    return {"g": pack(""), "f": pack("val_"), "g0": pack("hyper0_"),
            "gi": pack("neumann_")}


class Run:
    """One run of a training cell; ``fault`` (``faults.py``) breaks the
    timed path underneath, for the tests and the fault readings."""

    def __init__(self, conf, traffic, seed, limits, fault=None,
                 t_start=None):
        self.conf, self.traffic, self.seed = conf, traffic, seed
        self.limits, self.fault = limits, fault
        self.c = dict(conf["model"])
        self.t_start = time.perf_counter() if t_start is None else t_start

    def stage(self, name):
        harness.stage(name, self.t_start)

    def setup(self):
        self.build()
        self.start(self.seed)

    def build(self):
        """The programs, which no seed changes."""
        from repro.launch.train import population_round_program
        from repro.models.params import abstract_params
        t = self.traffic
        arch, fed, tr, specs, axes = program(self.conf, self.traffic)
        W.check_layout(self.c, abstract_params(tr.specs, arch.dtype))
        self.tr, self.specs = tr, specs
        n, c, q = t["population"], t["cohort"], t["q"]
        self.n, self.cohort, self.q = n, c, q
        scale = t["frames_scale"]
        self.gen_q = make_batch_fn(specs, arch.vocab, scale, (q, c))
        self.gen_n = make_batch_fn(specs, arch.vocab, scale, (n,))
        self.init = init_fn(tr, n)
        fn = population_round_program(tr, n, specs, axes)
        if self.fault is not None:
            fn = self.fault(fn, self)
        self.fn = fn
        self.tokens_per_round = tokens_per_round(specs, q)
        self.ref = None

    def start(self, seed):
        """Weights, batches and the population for ``seed``; then rounds
        0-2, the ones the reference follows."""
        self.seed, n, c = seed, self.n, self.cohort
        self.params = W.make(self.c, harness.jax_key(seed, SALT_WEIGHTS))
        self.data_key = harness.jax_key(seed, SALT_DATA)
        self.run_key = harness.jax_key(seed, SALT_RUN)
        self.cohorts = Cohorts(harness.np_rng(seed, SALT_COHORT), n, c)
        self.init_keys = jax.random.split(self.run_key, n)
        self.init_batch = self.gen_n(jax.random.fold_in(self.data_key,
                                                        INIT_BATCH))
        self.stage("weights and batches")
        self.state = self.init(self.params, self.init_batch, self.init_keys)
        jax.block_until_ready(self.state)
        self.stage("population initialised")
        self.snap = {}
        for r in range(CHECK_ROUNDS):
            self.round(r)
            row = jax.tree.map(lambda a: a[0], self.state[0])
            if r == 0:
                self.snap["grad"] = leaf_norms({"v": row["v"],
                                                "w": row["w"]})
            self.snap[r] = jax.device_get({"x": row["x"], "y": row["y"]})
            self.stage(f"round {r}")
        row = jax.tree.map(lambda a: a[0], self.state[0])
        self.snap["change"] = leaf_norms(jax.tree.map(
            jnp.subtract, {"x": row["x"], "y": row["y"]}, self.params))
        self.next_round = CHECK_ROUNDS

    def hlo_texts(self):
        """Compiled HLO of the programs a window runs (from the compile
        cache), for the trace's name stacks and kernels."""
        bank, last, server = self.state
        r = self.next_round
        key = jax.random.fold_in(self.data_key, r)
        return [self.fn.lower(bank, last, None, server,
                              jnp.asarray(self.cohorts(r), jnp.int32),
                              self.batch(r), self.run_key,
                              jnp.int32(r)).compile().as_text(),
                self.gen_q.lower(key).compile().as_text()]

    def batch(self, r):
        return self.gen_q(jax.random.fold_in(self.data_key, r))

    def round(self, r):
        bank, last, server = self.state
        ids = jnp.asarray(self.cohorts(r), jnp.int32)
        bank, last, _, server = self.fn(bank, last, None, server, ids,
                                        self.batch(r), self.run_key,
                                        jnp.int32(r))
        jax.block_until_ready(bank)
        self.state = (bank, last, server)

    def window(self, seconds):
        """Rounds until ``seconds`` have passed; (rounds, elapsed s)."""
        done, ends = 0, []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.round"):
                self.round(self.next_round)
            self.next_round += 1
            done += 1
            ends.append(time.perf_counter())
        elapsed = ends[-1] - t0
        took = np.diff([t0] + ends)
        print(f"window rounds {done}: seconds each min {took.min():.4f} "
              f"median {np.median(took):.4f} max {took.max():.4f}",
              file=sys.stderr, flush=True)
        return done, elapsed

    def counts(self, rounds):
        """What the per-layer readers need besides the trace."""
        t, c = self.traffic, self.c
        sizes = {k: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
            v)) for k, v in W.abstract(c).items()}
        per = {"g": "tokens", "f": "val_tokens", "g0": "hyper0_tokens",
               "gi": "neumann_tokens"}
        batch = {}
        for part, key in per.items():
            s = self.specs[key]
            n_seq = int(np.prod(s.shape[1:-1]))
            enc = self.specs.get(key.replace("tokens", "enc_embeds"))
            batch[part] = (n_seq, int(s.shape[-1]),
                           int(enc.shape[-2]) if enc is not None else 0)
        fed = dict(self.conf["fed"], cohort=self.cohort, q=self.q)
        storm = [counts.storm_update(sizes["y"], c["dtype"]),
                 counts.storm_update(sizes["x"], c["dtype"])]
        per_round = self.cohort * self.q
        return {"rounds": rounds,
                "round_flops": counts.fed_round_flops(c, fed, batch),
                "storm_flops": per_round * sum(s["flops"] for s in storm),
                "storm_bytes": per_round * sum(s["bytes"] for s in storm)}

    def free(self):
        self.state = None

    def check(self, checks: harness.Checks):
        """Follow rounds 0-2 with the reference and compare."""
        from reference.fed_bilevel import FedReference
        if self.ref is None:
            self.ref = FedReference(self.c, dict(self.conf["fed"], q=self.q),
                                    self.n)
        ref = self.ref
        ref.init(self.params, ref_batches(self.init_batch), self.init_keys)
        self.stage("reference initialised")
        run_key = self.run_key
        depth_key = lambda gid, t: jax.random.fold_in(
            jax.random.fold_in(run_key, gid), t)
        held = ref_batches(self.gen_q(jax.random.fold_in(self.data_key,
                                                         HELD_BATCH)))
        held = jax.tree.map(lambda a: a[0, 0], held["f"])
        dither = harness.jax_key(self.seed, SALT_DITHER)
        loss_gap, states = 0.0, []
        for r in range(CHECK_ROUNDS):
            st = ref.round(self.cohorts(r), ref_batches(self.batch(r)),
                           jax.random.fold_in(dither, r), depth_key)
            states.append(st)
            self.stage(f"reference round {r}")
            lr = ref.loss(st["x"], st["y"], held)
            lp = ref.loss(self.snap[r]["x"], self.snap[r]["y"], held)
            loss_gap = max(loss_gap, abs(lp - lr) / max(abs(lr), 1e-30))
        g_ref = leaf_norms({"v": states[0]["v"], "w": states[0]["w"]})
        grad_gap = worst_gap(self.snap["grad"], g_ref)
        x0 = jax.tree.map(lambda p: p.astype(jnp.float32), self.params)
        ch_ref = leaf_norms(jax.tree.map(
            jnp.subtract, {"x": states[-1]["x"], "y": states[-1]["y"]}, x0))
        # leaves whose reference gradient is nought to rounding move by
        # round-off alone: left out by a rule on that gradient
        gw = leaf_norms({"x": states[0]["w"], "y": states[0]["v"]})
        med = float(np.median(gw))
        keep = [i for i, g in enumerate(gw) if g >= 1e-3 * med]
        change_gap = worst_gap(self.snap["change"], ch_ref, keep)
        lim = self.limits
        ref.release()
        checks.add("loss_gap", loss_gap, lim["loss_gap"])
        checks.add("grad_gap", grad_gap, lim["grad_gap"])
        checks.add("change_gap", change_gap, lim["change_gap"])


class Cohorts:
    """Cohort of round r: c distinct clients of n, sorted, drawn from the
    seed in round order."""

    def __init__(self, rng, n, c):
        self.rng, self.n, self.c, self.drawn = rng, n, c, []

    def __call__(self, r):
        while len(self.drawn) <= r:
            self.drawn.append(np.sort(self.rng.choice(self.n, self.c,
                                                      replace=False)))
        return self.drawn[r]


def run(ctx):
    r = Run(ctx.conf, ctx.traffic, ctx.seed, ctx.limits, fault=ctx.fault,
            t_start=ctx.t_start)
    r.setup()
    setup_s = time.perf_counter() - ctx.t_start
    out = {"failed": 0}
    if ctx.trace:
        indexes = dict(tracing.hlo_index(t) for t in r.hlo_texts())
        (rounds, elapsed), ev, out["window_s"] = tracing.capture(
            lambda: r.window(min(ctx.seconds, TRACE_SECONDS)), ctx.trace_dir)
        out["events"] = tracing.annotate(ev, indexes)
        out["counts"] = r.counts(rounds)
    else:
        rounds, elapsed = r.window(ctx.seconds)
    out["attempted"] = rounds
    out["e2e"] = {"train_tokens_per_s": rounds * r.tokens_per_round / elapsed,
                  "setup_s": setup_s}
    out["device"] = harness.device_info(ctx.devs)
    r.free()
    r.stage("window closed")
    out["checks"] = harness.Checks()
    r.check(out["checks"])
    r.stage("compared")
    return out
