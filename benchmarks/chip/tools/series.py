"""Run one cell several times, one process per run, and summarise.

    python3 benchmarks/chip/tools/series.py --workload <cell> \
        --seeds 11 12 13 --seconds 30 [--trace 0 1 ...] [--out FILE]

Each run is ``run.py`` in a child process of its own (this parent never
touches JAX, so the child owns the chip). Every result line goes to
``--out`` as JSON lines with the run's seed, trace flag, exit code, wall
time and the tail of its standard error; the summary printed at the end
gives, per metric, the median and the quartile spread as a share of the
median (Python's ``statistics.quantiles(values, n=4)``).
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, nargs="*", default=[0],
                    help="trace flag per seed (the last one repeats)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="seconds a run may take before it is killed")
    a = ap.parse_args()
    rows = []
    for i, seed in enumerate(a.seeds):
        tr = a.trace[min(i, len(a.trace) - 1)]
        cmd = [sys.executable, str(HERE / "run.py"), "--workload",
               a.workload, "--seed", str(seed), "--seconds", str(a.seconds),
               "--trace", str(tr)]
        t0 = time.time()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=a.timeout)
        except subprocess.TimeoutExpired as e:      # the child is killed
            p = subprocess.CompletedProcess(cmd, 124, e.stdout or "",
                                            e.stderr or "")
            p.stdout = p.stdout if isinstance(p.stdout, str) else \
                p.stdout.decode(errors="replace")
            p.stderr = p.stderr if isinstance(p.stderr, str) else \
                p.stderr.decode(errors="replace")
        wall = time.time() - t0
        lines = p.stdout.strip().splitlines()
        res = None
        if p.returncode == 0 and lines:
            try:
                res = json.loads(lines[-1])
            except json.JSONDecodeError:
                res = None
        row = {"workload": a.workload, "seed": seed, "trace": tr,
               "rc": p.returncode, "wall_s": wall, "result": res,
               "stderr_tail": p.stderr[-6000:]}
        rows.append(row)
        print(json.dumps({k: row[k] for k in ("seed", "trace", "rc",
                                              "wall_s")}), flush=True)
        if res is not None:
            print(json.dumps(res), flush=True)
        else:
            print(p.stderr[-3000:], flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    for tr in sorted({r["trace"] for r in rows}):
        got = [r["result"] for r in rows if r["trace"] == tr and r["result"]]
        names = sorted({k for g in got for k in g["metrics"]})
        for n in names:
            v = [g["metrics"][n]["value"] for g in got if n in g["metrics"]]
            print(f"trace={tr} {n}: n={len(v)} median="
                  f"{statistics.median(v)!r} spread={spread(v)!r} "
                  f"values={v}", flush=True)
        print(f"trace={tr} correct: {[g['correct'] for g in got]}",
              flush=True)


if __name__ == "__main__":
    main()
