"""Readings of the numbers that decide ``correct``, over many seeds in one
process: sound runs, the control, and planted faults. The limits in
``limits/<cell>.json`` are set from these readings.

    # training: sound runs, the bfloat16 control, a planted fault
    python3 benchmarks/chip/tools/controls.py --workload <train cell> \
        --seeds 1 2 3 [--dtype bfloat16] [--fault half_cohort]
    # serving: each seed's window, then the served tokens' gaps and the
    # float8 control's gaps on the same sampled positions
    python3 benchmarks/chip/tools/controls.py --workload <serve cell> \
        --seeds 1 2 3 --seconds 20

Training readings need no measured window: each seed runs the set-up's
three checked rounds and the comparison. The programs are built once and
reused for every seed. One JSON line per seed, with each number and
``correct`` as the committed limits judge it; a serving line judges the
float8 control's gap by the same limit as ``control_correct``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import faults  # noqa: E402
import harness  # noqa: E402

def train(w, conf, traffic, a):
    if a.dtype:
        conf["model"]["dtype"] = a.dtype
    drv = harness.load_module(HERE / "drivers" / "train_population.py")
    r = drv.Run(conf, traffic, a.seeds[0], a.limits,
                fault=faults.TRAIN[a.fault] if a.fault else None)
    r.build()
    for seed in a.seeds:
        r.start(seed)
        r.free()
        checks = harness.Checks()
        r.check(checks)
        r.params = r.init_batch = None
        print(json.dumps({"seed": seed, "dtype": conf["model"]["dtype"],
                          "fault": a.fault, **{c["name"]: c["value"]
                                               for c in checks.items},
                          "correct": checks.correct}), flush=True)


def serve(w, conf, traffic, a):
    import numpy as np
    import weights as W
    from reference.decoder_lm import served_gaps
    drv = harness.load_module(HERE / "drivers" / "serve_engine.py")
    r = drv.Run(conf, traffic, a.seeds[0], a.limits,
                fault=faults.SERVE[a.fault] if a.fault else None)
    r.setup(a.seconds)
    done = []
    step = r.engine.step
    r.engine.step = lambda: done.extend(step()) or []
    import loadgen
    for seed in a.seeds:
        if seed != a.seeds[0]:
            r.seed = seed
            r.params = r.engine.params = None      # one set on the chip
            r.params = W.make(r.c, harness.jax_key(seed, drv.SALT_WEIGHTS))
            r.engine.params = r.params
            r.requests = loadgen.generate(
                traffic, a.seconds, harness.np_rng(seed, drv.SALT_TRAFFIC),
                conf["model"]["vocab"])
        done.clear()
        r.window(a.seconds, traffic["drain_s"])
        outputs = drv.outputs_of(done)
        while r.engine.has_work:        # the next seed starts on free slots
            r.engine.step()
        pick = drv.sample(r, outputs)
        got = {"seed": seed, "fault": a.fault, "requests": len(pick),
               "tokens": sum(len(outputs[q.rid]) for q in pick)}
        for prefix, lower in (("", False), ("control_", True)):
            checks = harness.Checks()
            checks.add("served_gap", max(float(np.max(served_gaps(
                r.c, r.params, q.tokens, outputs[q.rid], lower=lower)))
                for q in pick), a.limits["served_gap"])
            got[prefix + "served_gap"] = checks.items[0]["value"]
            got[prefix + "correct"] = checks.correct
        print(json.dumps(got), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--fault", default=None)
    a = ap.parse_args()
    _, w = harness.cell(a.workload)
    conf = harness.load_json("configs", w["config"])
    traffic = harness.load_json("traffic", w["traffic"])
    a.limits = harness.load_json("limits", w["name"])
    harness.require_chips(w["chips"])
    harness.use_compile_cache()
    {"train_population": train, "serve_engine": serve}[conf["driver"]](
        w, conf, traffic, a)


if __name__ == "__main__":
    main()
