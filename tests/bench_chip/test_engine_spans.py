"""The serving engine's spans in the benchmark's traces, and the two
per-layer metrics that read them (``metrics/serve.admit_overhead_ms.py``,
``metrics/serve.tick_idle_ms.py``): each reader on hand-made events whose
value is worked out by hand, and a traced CPU run of the overload cell
whose host spans nest as the engine opens them.

    python3 -m pytest -q tests/bench_chip/test_engine_spans.py
"""
from __future__ import annotations

from types import SimpleNamespace

import pytest
from bench_cells import CHIP, OVERLOAD, driver, small_ctx

import harness

ADMIT = harness.load_module(CHIP / "metrics" / "serve.admit_overhead_ms.py")
TICK_IDLE = harness.load_module(CHIP / "metrics" / "serve.tick_idle_ms.py")
MS = 1_000_000                         # nanoseconds


def host(name, start, end):
    return {"plane": "/host:CPU", "line": "python", "name": name,
            "start_ns": round(start * MS),
            "dur_ns": round(end * MS) - round(start * MS),
            "stats": {}}


def device(line, name, start, end):
    return dict(host(name, start, end), plane="/device:TPU:0", line=line)


def layer(events):
    return SimpleNamespace(events=events)


# the traced window is 1 ms .. 10 ms
WINDOW = host("bench.window", 1, 10)


def test_admit_overhead_by_hand():
    """Admissions at 2-3, 4-4.8 and 6-7.5 ms hold prefills of 0.5, 0.4
    and 0.3 ms: 0.5, 0.4 and 1.2 ms are left, median 0.5. A prefill
    belongs to the admission that holds its middle: the one at 3.9-4.3
    starts before its admission (as a device clock 0.1 ms early places
    it) and counts whole; the one at 4.6-5.4 does not count. The decode
    program inside the third is no prefill, and the admissions that cross
    the window's edges are dropped."""
    ev = [WINDOW,
          host("bench.serve.admit", 0.5, 1.1),
          host("bench.serve.admit", 2, 3),
          device("XLA Modules", "jit_prefill_fn(1)", 2.1, 2.6),
          host("bench.serve.admit", 4, 4.8),
          device("XLA Modules", "jit_prefill_fn(2)", 3.9, 4.3),
          device("XLA Modules", "jit_prefill_fn(2)", 4.6, 5.4),
          host("bench.serve.admit", 6, 7.5),
          device("XLA Modules", "jit_prefill_fn(1)", 6.1, 6.4),
          device("XLA Modules", "jit_decode_fn(3)", 7.0, 7.4),
          host("bench.serve.admit", 9.5, 10.5),
          device("XLA Modules", "jit_prefill_fn(1)", 9.6, 9.7)]
    assert ADMIT.read(layer(ev)) == pytest.approx(0.5)
    # a parent engine opens no admission span; a trace with no device
    # program is no device reading
    assert ADMIT.read(layer([e for e in ev if "admit" not in e["name"]])) \
        is None
    assert ADMIT.read(layer([e for e in ev if "prefill" not in e["name"]])) \
        is None


def test_tick_idle_by_hand():
    """Ticks at 1.2-1.4, 2-5 and 5-9 ms; an op belongs to the tick that
    holds its middle. The first holds none: 0.2 ms idle. The second holds
    one begun before it (1.9-2.3), two overlapping ops and one nested in
    them (2.5-4) and one that runs past its end (4.6-5.2): busy 2.5, idle
    0.5. The third holds a loop with an op nested in it (5.5-8.5): busy 3,
    idle 1. The median is 0.5; the op at 1.5-2.2 lies in no tick, and the
    tick that crosses the window's end is dropped."""
    ops = [(1.5, 2.2), (1.9, 2.3), (2.5, 3.5), (2.6, 3.0), (3.2, 4.0),
           (4.6, 5.2), (5.5, 8.5), (6.0, 7.0), (9.6, 9.9)]
    ev = [WINDOW] + [host("bench.serve.tick", a, b) for a, b in
                     [(1.2, 1.4), (2, 5), (5, 9), (9.5, 10.5)]] + \
        [device("XLA Ops", f"fusion.{i}", a, b) for i, (a, b) in
         enumerate(ops)]
    assert TICK_IDLE.read(layer(ev)) == pytest.approx(0.5)
    assert TICK_IDLE.read(layer([e for e in ev if "tick" not in e["name"]])) \
        is None
    assert TICK_IDLE.read(layer([e for e in ev if "fusion" not in
                                 e["name"]])) is None


def test_traced_run_holds_the_span_tree():
    """A traced CPU run of the overload cell: in the profiler's host
    events each admission's ``bench.serve.admit`` lies inside a
    ``bench.serve.tick`` and holds one ``bench.serve.prefill``."""
    import tracing
    ctx = small_ctx(OVERLOAD, trace=True)
    ctx.seconds = 3.0
    drv = driver(ctx)
    drv.TRACE_SECONDS, drv.TRACE_END_BEFORE = 1.5, 1.0
    r = drv.Run(ctx.conf, ctx.traffic, ctx.seed, ctx.limits)
    r.setup(ctx.seconds)
    r.window(ctx.seconds, ctx.traffic["drain_s"],
             tracing.Tracer(ctx.trace_dir))

    def spans(name):
        return [(e["start_ns"], e["start_ns"] + e["dur_ns"])
                for e in r.events if e["name"] == "bench.serve." + name]

    def inside(a, b):
        return [x for x in b if x[0] <= a[0] and a[1] <= x[1]]

    ticks, admits, prefills = spans("tick"), spans("admit"), spans("prefill")
    assert ticks and admits and len(prefills) == len(admits)
    for a in admits:
        assert len(inside(a, ticks)) == 1
        assert len([p for p in prefills if inside(p, [a])]) == 1
