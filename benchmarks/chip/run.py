"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration
(``configs/<config>.json``, whose ``driver`` picks ``drivers/<driver>.py``)
and a traffic mix (``traffic/<traffic>.json``); the limits its outputs are
held to sit in ``limits/<cell>.json``. The run makes its weights and inputs
from ``--seed``, warms every program the window uses (set-up), measures for
``--seconds``, then checks what the timed path produced against the plain
reference. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of a short steady window, and
the per-layer metrics that the readers in ``metrics/<name>.py`` take from
it. The last line of standard output is the result as one JSON object;
without a TPU, or with fewer chips than the cell asks for, the run exits
nonzero and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from process start

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402


def applies(metric, cell_name, e2e_names):
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def context(args, fault=None, devs=None):
    """Everything a driver needs for one run of the named cell."""
    manifest, cell = harness.cell(args.workload)
    conf = harness.load_json("configs", cell["config"])
    return SimpleNamespace(
        manifest=manifest, cell=cell, conf=conf,
        traffic=harness.load_json("traffic", cell["traffic"]),
        limits=harness.load_json("limits", cell["name"]),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, devs=devs, fault=fault,
        trace_dir=harness.ROOT / ".bench_trace")


def result(ctx, out):
    """The result line's keys from a driver's output."""
    cell, manifest = ctx.cell, ctx.manifest
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    metrics = {}
    device = dict(out["device"])
    res = {"attempted": out["attempted"], "failed": out["failed"]}
    if not ctx.trace:
        for m in e2e:
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    else:
        import tracing as T
        ev = out["events"]
        layer = SimpleNamespace(
            events=ev, window_s=out["window_s"], counts=out["counts"],
            conf=ctx.conf, traffic=ctx.traffic,
            peaks=harness.peaks(device["kind"]))
        for m in manifest["per_layer"]:
            if not applies(m, cell["name"], e2e_names):
                continue
            reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py")
            v = reader.read(layer)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = T.busy_s(ev)
        device["window_s"] = out["window_s"]
        res["breakdown"] = {"device_ops": T.top_ops(ev),
                            "idle_gaps": T.idle_gaps(ev)}
    res["metrics"] = metrics
    res["device"] = device
    return res


def main(argv=None):
    args = parse(argv)
    ctx = context(args)
    ctx.devs = harness.require_chips(ctx.cell["chips"])
    harness.stage("devices", T_START)
    harness.use_compile_cache()
    driver = harness.load_module(HERE / "drivers" /
                                 f"{ctx.conf['driver']}.py")
    out = driver.run(ctx)
    harness.emit(result(ctx, out), out["checks"])


if __name__ == "__main__":
    main()
