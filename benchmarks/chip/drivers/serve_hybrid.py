"""Driver for the hybrid serving cell (Mamba-2 and attention layers with
an MoE block in every layer): ``serve_engine.py``'s run, with this
configuration's own model, weights, reference and counts.

Everything but those four is ``serve_engine.py``'s, loaded from its file:
the set-up and warm-up, the open-loop window and its traced slice (a
shorter one here, ``SE.TRACE_SECONDS`` below), the recorder, the
metrics, the sample of requests that is checked, and the result. The
configuration file holds the published config's keys at its
top level (``weights_hybrid.sizes`` reads them), so the program's
``ArchConfig`` is built here from the whole file.

What is compared (``limits/<cell>.json``): ``served_gap``, as in
``serve_engine.py``, against ``reference/granite_hybrid.py``.

Two tools of its own, since ``tools/`` load ``serve_engine.py`` by name:

    # sound runs, the float8 control and planted faults, one set-up
    python3 benchmarks/chip/drivers/serve_hybrid.py controls \\
        --workload <cell> --seeds 1 2 3 --seconds 20 \\
        [--faults frozen_cache altered_token]
    # the knee: each rate replayed open-loop for --seconds
    python3 benchmarks/chip/drivers/serve_hybrid.py sweep \\
        --workload <cell> --rates 8 12 16 20 --seconds 30 --seed 7
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import counts  # noqa: E402
import counts_hybrid  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import weights_hybrid as WH  # noqa: E402

SE = harness.load_module(HERE / "drivers" / "serve_engine.py")


def arch_config(c):
    """The program's ``ArchConfig`` for the configuration file: the
    registry entry it names, with the file's numbers."""
    import dataclasses
    from repro.configs import get_arch
    base = get_arch(c["arch"])
    s = WH.sizes(c)
    return dataclasses.replace(
        base, n_layers=s["L"], d_model=s["d"], n_heads=s["h"],
        n_kv_heads=s["kv"], head_dim=s["hd"], vocab=s["vocab"],
        norm_eps=c["rms_norm_eps"], dtype=c["dtype"],
        layer_types=tuple(c["layer_types"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        attention_multiplier=float(c["attention_multiplier"]),
        use_rope=c["position_embedding_type"] != "nope",
        tie_embeddings=bool(c["tie_word_embeddings"]),
        moe=dataclasses.replace(
            base.moe, n_experts=s["e"], n_routed=s["routed"],
            first_expert=c["first_expert"], top_k=c["num_experts_per_tok"],
            d_ff_expert=s["f"], d_ff_shared=s["fs"]),
        ssm=dataclasses.replace(
            base.ssm, state_dim=c["mamba_d_state"],
            expand=c["mamba_expand"], conv_width=s["cw"],
            head_dim=s["p"]))


class Run(SE.Run):
    def __init__(self, conf, traffic, seed, limits, fault=None,
                 t_start=None):
        self.conf, self.traffic, self.seed = conf, traffic, seed
        self.limits, self.fault = limits, fault
        self.c = conf
        self.t_start = time.perf_counter() if t_start is None else t_start

    def setup(self, seconds):
        from repro.models.model import model_specs
        from repro.models.params import abstract_params
        from repro.serve.engine import Engine
        s, t = self.conf["serve"], self.traffic
        arch = arch_config(self.c)
        WH.check_layout(self.c, abstract_params(model_specs(arch),
                                                arch.dtype))
        self.params = WH.make(self.c, harness.jax_key(self.seed,
                                                      SE.SALT_WEIGHTS))
        jax.block_until_ready(self.params)
        harness.stage("weights", self.t_start)
        self.requests = loadgen.generate(
            t, seconds, harness.np_rng(self.seed, SE.SALT_TRAFFIC),
            arch.vocab)
        self.engine = Engine(arch, self.params, slots=s["slots"],
                             max_len=s["max_len"], kv_quant=s["kv_quant"],
                             kv_kernel=s["kv_kernel"])
        if self.fault is not None:
            self.fault(self.engine, self)
        harness.stage("engine built", self.t_start)
        self.warm(arch)

    def reseed(self, seed, seconds):
        """New weights and traffic for ``seed`` on the built engine."""
        self.seed = seed
        self.params = self.engine.params = None    # one set on the chip
        self.params = WH.make(self.c, harness.jax_key(seed, SE.SALT_WEIGHTS))
        self.engine.params = self.params
        self.requests = loadgen.generate(
            self.traffic, seconds, harness.np_rng(seed, SE.SALT_TRAFFIC),
            self.c["vocab_size"])

    def gap(self, outputs, lower=False):
        from reference.granite_hybrid import served_gaps
        pick = SE.sample(self, outputs)
        return max((float(np.max(served_gaps(self.c, self.params, r.tokens,
                                             outputs[r.rid], lower=lower)))
                    for r in pick), default=math.inf), pick

    def check(self, checks, outputs):
        checks.add("served_gap", self.gap(outputs)[0],
                   self.limits["served_gap"])

    def counts(self):
        """Flops of what the traced slice ran, the int8 kernel's and the
        mixers' bytes and flops of its decode ticks."""
        rec, c = self.rec, self.c
        s = WH.sizes(c)
        k_on, k_off = self.traced
        out = dict.fromkeys(("model_flops", "qd_bytes", "qd_flops",
                             "mamba_bytes", "mamba_flops"), 0.0)
        active = {}
        for i, r in enumerate(self.submitted):
            ticks = rec.token_ticks(i, r.rid)
            if not ticks:
                continue
            plen = len(r.tokens)
            if k_on <= ticks[0] < k_off:
                out["model_flops"] += counts_hybrid.prefill_flops(c, plen)
            for j, t in enumerate(ticks[1:], start=1):
                if k_on <= t < k_off:
                    active.setdefault(t, []).append(plen + j)
        for lens in active.values():
            out["model_flops"] += counts_hybrid.decode_flops(
                c, [p - 1 for p in lens])
            qd = counts.quant_decode(lens, s["h"], s["kv"], s["hd"])
            out["qd_bytes"] += s["La"] * qd["bytes"]
            out["qd_flops"] += s["La"] * qd["flops"]
            m = counts_hybrid.mamba_tick(c, len(lens))
            out["mamba_bytes"] += m["bytes"]
            out["mamba_flops"] += m["flops"]
        return out


# serve_engine's run() builds this file's Run: SE is this driver's own
# instance of serve_engine.py
SE.Run = Run
run = SE.run
# A decode tick of this model is many more device ops than the dense
# stage's: stopping the profiler after serve_engine's 20 s slice stalled
# the replay 41 s on a v5e, which with the overload backlog comes near
# ``drain_s``. 10 s still holds about 150 ticks and 70 admissions.
SE.TRACE_SECONDS = 10.0


# ---------------------------------------------------------------- tools

def _cell(a):
    _, w = harness.cell(a.workload)
    conf = harness.load_json("configs", w["config"])
    traffic = harness.load_json("traffic", w["traffic"])
    harness.require_chips(w["chips"])
    harness.use_compile_cache()
    return w, conf, traffic


def controls(a):
    """One line per seed and fault: the sound run's gap and the float8
    control's gap on the same sampled requests (``fault`` null), then
    each planted fault's gap, each with ``correct`` as the committed
    limit judges it."""
    import faults
    w, conf, traffic = _cell(a)
    limits = harness.load_json("limits", w["name"])
    r = Run(conf, traffic, a.seeds[0], limits)
    r.setup(a.seconds)
    eng = r.engine
    done = []
    step = eng.step
    eng.step = lambda: done.extend(step()) or []
    decode, argmax = eng._decode, eng._argmax
    for seed in a.seeds:
        if seed != r.seed:
            r.reseed(seed, a.seconds)
        for fault in [None] + a.faults:
            eng._decode, eng._argmax = decode, argmax
            if fault:
                faults.SERVE[fault](eng, r)
            done.clear()
            r.window(a.seconds, traffic["drain_s"])
            while eng.has_work:     # the next run starts on free slots
                eng.step()
            outputs = SE.outputs_of(done)
            got = {"seed": seed, "fault": fault}
            for prefix, lower in (("", False), ("control_", True)):
                if lower and fault:
                    continue
                gap, pick = r.gap(outputs, lower)
                got.update({"requests": len(pick), prefix + "served_gap": gap,
                            prefix + "correct": gap <= limits["served_gap"]})
            print(json.dumps(got), flush=True)


def sweep(a):
    """As ``tools/sweep.py``, on this driver's Run."""
    w, conf, traffic = _cell(a)
    r = Run(conf, traffic, a.seed, {})
    r.setup(a.seconds)
    for rate in a.rates:
        t = dict(traffic, rate=rate)
        r.requests = loadgen.generate(t, a.seconds, harness.np_rng(
            a.seed, int(rate * 100)), conf["vocab_size"])
        r.window(a.seconds, 0.0)
        m, failed = r.metrics(a.seconds)
        queue, active = r.engine.pending, r.engine.active
        while r.engine.has_work:
            r.engine.step()
        print(json.dumps({"rate": rate, "arrived": len(r.submitted),
                          "unfinished_at_close": failed,
                          "queued_at_close": queue,
                          "active_at_close": active,
                          "tokens_per_s": m["serve_tokens_per_s"],
                          "ttft_p95_ms": m["ttft_p95_ms"],
                          "itl_p95_ms": m["itl_p95_ms"],
                          "max_lateness_s": max(r.late or [0.0])}),
              flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="tool", required=True)
    c = sub.add_parser("controls")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--seconds", type=float, default=20.0)
    c.add_argument("--faults", nargs="*", default=[])
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", type=float, nargs="+", required=True)
    s.add_argument("--seconds", type=float, default=30.0)
    s.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()
    {"controls": controls, "sweep": sweep}[a.tool](a)


if __name__ == "__main__":
    main()
