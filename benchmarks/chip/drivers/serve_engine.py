"""Driver for serving cells: open-loop traffic through the continuous
batching engine (``serve.Engine``) with the int8 KV pool.

Set-up makes the weights on the device from the seed, builds the engine,
and warms every program the window will run: one prefill per prompt
bucket of the traffic file, the int8 quantise-and-scatter of a cache row,
and the decode tick at full slot count. The window replays the traffic's
arrivals for ``--seconds`` against the engine clock; afterwards the engine
drains what arrived until every request is answered, for at most the
traffic's ``drain_s`` (long enough for the backlog of the overload mix and
a 512-token answer admitted last), so a late answer counts as late, with
its wait in the latencies, and not as failed.

Token times come from the ticks: a request's first token and its second
are delivered at the end of the tick that admitted it, every later token at
the end of one later tick each. ``itl_p95_ms`` is taken over every gap
between two tokens of one request that arrived in the window
(``ttft_p95_ms``, from each scheduled arrival to its first token, is
computed too, for ``tools/sweep.py``). A request not finished when the
replay stops (the window and then at most ``drain_s``) counts as
``failed``, which a sound run never reaches; its tokens so far count, and
one never admitted takes the time until the replay gave up as its first
token's. ``serve_tokens_per_s`` counts the tokens delivered by ticks
that ended inside the window.

A traced run replays the same window and profiles a slice of it: the
``TRACE_SECONDS`` that end a second before arrivals stop, so the engine
is as loaded as the cell makes it, arrivals still come in, and no drain
falls inside. The per-layer counts take the ticks of that slice only.
The slice is long enough to hold some tens of admissions, whose prompt
lengths set the prefill share. Stopping the profiler stalls the replay
for seconds (12-13 s after a 20 s slice on a v5e); only the arrivals of
the last second, after the slice, wait through it.

What is compared (``limits/<cell>.json``): ``served_gap``, the widest gap
by which a served token's logit lies below the best logit of the float32
reference at its position, in units of the standard deviation of the
reference's logits there, over a sample drawn from the seed of the
requests the window finished, with the longest among them and some
hundreds of served tokens in all.
"""
from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import counts
import harness
import loadgen
import tracing
import weights as W

SALT_WEIGHTS, SALT_TRAFFIC, SALT_SAMPLE = 1, 6, 7
SAMPLE_TOKENS, SAMPLE_MIN, SAMPLE_MAX = 600, 6, 12
TRACE_SECONDS, TRACE_END_BEFORE = 20.0, 1.0


class Recorder:
    """The engine's telemetry hook: the end of every tick, the tick of
    every admission (the engine admits in submission order, and opens one
    ``serve.prefill`` span per admission), and each retired request's
    token count."""

    def __init__(self, engine_now):
        from repro.obs.telemetry import NullTelemetry
        self.null = NullTelemetry()
        self.now = engine_now
        self.tick_end = []
        self.admit_tick = []           # admission i -> tick index
        self.done = {}                 # rid -> tokens delivered

    # the Telemetry interface the engine calls
    def __getattr__(self, name):
        return getattr(self.null, name)

    def request(self, rid, **f):
        self.done[rid] = f["new_tokens"]

    def tick(self, tick, **f):
        self.tick_end.append(self.now())

    def span(self, name):
        if name == "serve.prefill":
            self.admit_tick.append(len(self.tick_end))
        return jax.profiler.TraceAnnotation("bench." + name)

    def token_ticks(self, i, rid):
        """Tick index of each token delivered to the i-th submitted
        request: tokens 0 and 1 at its admission tick, then one a tick."""
        if i >= len(self.admit_tick):
            return []
        k = self.admit_tick[i]
        n = self.done[rid] if rid in self.done else \
            len(self.tick_end) - k + 1
        return [k] + [k + j - 1 for j in range(1, n)]


class Run:
    def __init__(self, conf, traffic, seed, limits, fault=None,
                 t_start=None):
        self.conf, self.traffic, self.seed = conf, traffic, seed
        self.limits, self.fault = limits, fault
        self.c = dict(conf["model"])
        self.t_start = time.perf_counter() if t_start is None else t_start

    def setup(self, seconds):
        from repro.models.model import model_specs
        from repro.models.params import abstract_params
        from repro.serve.engine import Engine
        s, t = self.conf["serve"], self.traffic
        arch = harness.arch_config(self.conf)
        W.check_layout(self.c, abstract_params(model_specs(arch),
                                               arch.dtype))
        self.params = W.make(self.c, harness.jax_key(self.seed, SALT_WEIGHTS))
        jax.block_until_ready(self.params)
        harness.stage("weights", self.t_start)
        self.requests = loadgen.generate(
            t, seconds, harness.np_rng(self.seed, SALT_TRAFFIC), arch.vocab)
        self.engine = Engine(arch, self.params, slots=s["slots"],
                             max_len=s["max_len"], kv_quant=s["kv_quant"],
                             kv_kernel=s["kv_kernel"])
        if self.fault is not None:
            self.fault(self.engine, self)
        harness.stage("engine built", self.t_start)
        self.warm(arch)

    def warm(self, arch):
        """Run every program shape of the window once: each bucket's
        prefill (with the row's quantise and scatter), and the decode tick
        over all slots. A later prefill overwrites a slot's whole cache row,
        so what the warm-up leaves in the pool is never read."""
        from repro.serve.engine import Request
        eng = self.engine
        for i, plen in enumerate(self.traffic["prompt_lens"]):
            eng.submit(Request(rid=-1 - i, tokens=np.zeros(plen, np.int32),
                               max_new_tokens=2))
        while eng.has_work:
            eng.step()
        jax.block_until_ready(eng._pool)

    def decode_hlo(self):
        """Compiled HLO of the decode tick (from the compile cache), for
        the kernel's events in the trace."""
        eng, b = self.engine, self.conf["serve"]["slots"]
        return eng._decode.lower(
            self.params, eng._pool, jnp.zeros((b, 1), jnp.int32),
            jnp.ones((b,), jnp.int32)).compile().as_text()

    def window(self, seconds, drain_s, tracer=None):
        """Replay the window; with ``tracer``, profile the slice that
        ends ``TRACE_END_BEFORE`` before arrivals stop (ticks
        ``self.traced``)."""
        eng = self.engine
        rec = self.rec = Recorder(eng.now)
        eng.tele = rec
        t_off = max(seconds - TRACE_END_BEFORE, seconds / 2)
        t_on = max(t_off - TRACE_SECONDS, 0.0)
        self.traced = None

        def on_tick():
            if tracer is None or self.traced is not None:
                return
            now = eng.now()
            if not tracer.started and now >= t_on:
                tracer.start()
                self.k_on = len(rec.tick_end)
            elif tracer.started and now >= t_off:
                self.stop_trace(tracer)
        submitted, late = loadgen.replay(eng, self.requests, seconds,
                                         drain_s, on_tick)
        if tracer is not None and self.traced is None:
            if not tracer.started:
                tracer.start()
                self.k_on = len(rec.tick_end)
            self.stop_trace(tracer)
        self.submitted, self.late = submitted, late
        return rec

    def stop_trace(self, tracer):
        self.traced = (self.k_on, len(self.rec.tick_end))
        self.events, self.window_s = tracer.stop()

    def metrics(self, seconds):
        rec = self.rec
        ttft, itl, tokens_in_window, failed = [], [], 0, 0
        for i, r in enumerate(self.submitted):
            times = [rec.tick_end[t] for t in rec.token_ticks(i, r.rid)]
            if r.rid not in rec.done:
                failed += 1
            # a request never admitted waited at least until the replay
            # gave up on it
            ttft.append((times[0] if times else rec.tick_end[-1])
                        - r.arrival_s)
            itl.extend(b - a for a, b in zip(times, times[1:]))
            tokens_in_window += sum(1 for x in times if x <= seconds)
        return {"ttft_p95_ms": 1e3 * harness.percentile(ttft, 95),
                "itl_p95_ms": 1e3 * harness.percentile(itl, 95),
                "serve_tokens_per_s": tokens_in_window / seconds}, failed

    def free(self):
        self.engine = None

    def check(self, checks, outputs):
        """Compare the sampled requests' served tokens with the float32
        reference."""
        from reference.decoder_lm import served_gaps
        pick = sample(self, outputs)
        gap = max((float(np.max(served_gaps(self.c, self.params, r.tokens,
                                            outputs[r.rid])))
                   for r in pick), default=math.inf)
        checks.add("served_gap", gap, self.limits["served_gap"])

    def counts(self):
        """Flops and kernel bytes of what the traced slice ran: the
        prefills admitted and the tokens decoded in its ticks."""
        rec, c = self.rec, self.c
        k_on, k_off = self.traced
        flops, qd_bytes, qd_flops = 0.0, 0.0, 0.0
        active = {}                     # tick -> [cache length after it]
        for i, r in enumerate(self.submitted):
            ticks = rec.token_ticks(i, r.rid)
            if not ticks:
                continue
            plen = len(r.tokens)
            if k_on <= ticks[0] < k_off:
                flops += counts.prefill_flops(c, plen)
            for j, t in enumerate(ticks[1:], start=1):   # decoded tokens
                if k_on <= t < k_off:
                    active.setdefault(t, []).append(plen + j)
        for tick, lens in active.items():
            flops += counts.decode_flops(c, [p - 1 for p in lens])
            qd = counts.quant_decode(lens, c["n_heads"], c["n_kv_heads"],
                                     c["head_dim"])
            qd_bytes += c["n_layers"] * qd["bytes"]
            qd_flops += c["n_layers"] * qd["flops"]
        return {"model_flops": flops, "qd_bytes": qd_bytes,
                "qd_flops": qd_flops}


def sample(run, outputs):
    """Finished requests to check, drawn from the seed: the one with the
    most served tokens, then others until there are at least
    ``SAMPLE_MIN`` requests and ``SAMPLE_TOKENS`` tokens (at most
    ``SAMPLE_MAX`` requests)."""
    rng = harness.np_rng(run.seed, SALT_SAMPLE)
    done = [r for r in run.submitted if r.rid in outputs]
    if not done:
        return []
    longest = max(done, key=lambda r: len(outputs[r.rid]))
    pick, n_tok = [longest], len(outputs[longest.rid])
    for i in rng.permutation(len(done)):
        if len(pick) >= SAMPLE_MAX or (n_tok >= SAMPLE_TOKENS and
                                       len(pick) >= SAMPLE_MIN):
            break
        if done[i] is not longest:
            pick.append(done[i])
            n_tok += len(outputs[done[i].rid])
    return pick


def outputs_of(engine_outputs):
    return {c.rid: c.tokens for c in engine_outputs}


def run(ctx):
    r = Run(ctx.conf, ctx.traffic, ctx.seed, ctx.limits, fault=ctx.fault,
            t_start=ctx.t_start)
    seconds = ctx.seconds
    r.setup(seconds)
    setup_s = time.perf_counter() - ctx.t_start
    harness.stage("set up", ctx.t_start)
    done = []
    step = r.engine.step
    r.engine.step = lambda: done.extend(step()) or []
    drain = ctx.traffic["drain_s"]
    out = {}
    if ctx.trace:
        indexes = dict([tracing.hlo_index(r.decode_hlo())])
        r.window(seconds, drain, tracing.Tracer(ctx.trace_dir))
        out["events"] = tracing.annotate(r.events, indexes)
        out["window_s"] = r.window_s
        out["counts"] = r.counts()
    else:
        r.window(seconds, drain)
    out["e2e"], out["failed"] = r.metrics(seconds)
    last = r.rec.tick_end[-1] if r.rec.tick_end else seconds
    print(f"drain: last tick {last - seconds:.2f} s after "
          f"arrivals stopped, {out['failed']} of {len(r.submitted)} "
          f"requests unfinished", file=sys.stderr, flush=True)
    if r.late:
        print(f"generator lateness: p99 {harness.percentile(r.late, 99):.4f}"
              f" s, max {max(r.late):.4f} s over {len(r.late)} submissions",
              file=sys.stderr, flush=True)
    out["e2e"]["setup_s"] = setup_s
    out["attempted"] = len(r.submitted)
    out["device"] = harness.device_info(ctx.devs)
    r.free()
    harness.stage("window closed", ctx.t_start)
    out["checks"] = harness.Checks()
    r.check(out["checks"], outputs_of(done))
    harness.stage("compared", ctx.t_start)
    return out
