"""Sweep the arrival rate of a serving cell's traffic once, to find the
knee: the highest rate the engine sustains without a growing queue.

    python3 benchmarks/chip/tools/sweep.py --workload <serve cell> \
        --rates 2 4 6 8 10 12 --seconds 30 --seed 7 [--traffic-seeds ...]

One process: the weights and programs are set up once, then each rate's
arrivals are replayed open-loop for ``--seconds`` (no drain inside the
measurement), and the engine is drained between rates. For each rate it
prints the requests that arrived and finished, the queue left at the close,
completed tokens per second, and the TTFT and inter-token p95 of the
requests that finished. The rate is fixed in the cell's traffic file from
this sweep; runs of the benchmark never search for one. With
``--traffic-seeds`` each rate is replayed once per seed (the arrivals that
``run.py`` would make for that seed): that reads how far a tail swings
from seed to seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--traffic-seeds", type=int, nargs="*", default=None)
    a = ap.parse_args()
    _, w = harness.cell(a.workload)
    conf = harness.load_json("configs", w["config"])
    traffic = harness.load_json("traffic", w["traffic"])
    harness.require_chips(w["chips"])
    harness.use_compile_cache()
    drv = harness.load_module(HERE / "drivers" / "serve_engine.py")
    r = drv.Run(conf, traffic, a.seed, {})
    r.setup(a.seconds)
    import loadgen
    runs = [(rate, None) for rate in a.rates] if a.traffic_seeds is None \
        else [(rate, s) for rate in a.rates for s in a.traffic_seeds]
    for rate, seed in runs:
        t = dict(traffic, rate=rate)
        rng = harness.np_rng(a.seed, int(rate * 100)) if seed is None \
            else harness.np_rng(seed, drv.SALT_TRAFFIC)
        r.requests = loadgen.generate(t, a.seconds, rng,
                                      conf["model"]["vocab"])
        r.window(a.seconds, 0.0)
        m, failed = r.metrics(a.seconds)
        queue = r.engine.pending
        active = r.engine.active
        while r.engine.has_work:
            r.engine.step()
        print(json.dumps({"rate": rate, "traffic_seed": seed,
                          "arrived": len(r.submitted),
                          "unfinished_at_close": failed,
                          "queued_at_close": queue,
                          "active_at_close": active,
                          "tokens_per_s": m["serve_tokens_per_s"],
                          "ttft_p95_ms": m["ttft_p95_ms"],
                          "itl_p95_ms": m["itl_p95_ms"],
                          "max_lateness_s": max(r.late or [0.0])}),
              flush=True)


if __name__ == "__main__":
    main()
