"""The comparison that decides ``correct`` has to fail a serving run that
is wrong.

Each test drives a whole run of a serving cell on the CPU at a small size
(``bench_cells.py``) and reads ``correct`` against the cell's committed
limits: a sound run comes out correct; a run with a fault planted in the
timed path does not: a decode tick that keeps its cache unchanged, or a
served token altered where it is produced. The control, the reference's
float8 ranking of the served positions, is read beside the sound gap. A
traced run profiles a slice of its window while arrivals still come in.

    python3 -m pytest -q tests/bench_chip/test_controls_serve.py
"""
from __future__ import annotations

from bench_cells import CHIP, OVERLOAD, SERVE, driver, run_cell, small_ctx
from faults import altered_token, frozen_cache

import harness


# ---------------------------------------------------------------- serving

def test_serve_sound_run_is_correct():
    assert run_cell(small_ctx(SERVE))


def test_serve_frozen_cache_is_not_correct():
    assert not run_cell(small_ctx(SERVE, fault=frozen_cache))


def test_serve_altered_token_is_not_correct():
    assert not run_cell(small_ctx(SERVE, fault=altered_token))


def test_serve_control_float8_reads_three_times_the_sound_gap():
    """The control ranks each served position in float8; the gap of its
    first choice in the float32 reference reads at least three times the
    gap of the served tokens on the same positions (at this size both are
    smaller than at the cell's; the limit lies between the two readings
    there)."""
    import numpy as np
    from reference.decoder_lm import served_gaps
    ctx = small_ctx(SERVE)
    drv = harness.load_module(CHIP / "drivers" / "serve_engine.py")
    r = drv.Run(ctx.conf, ctx.traffic, ctx.seed, ctx.limits)
    r.setup(ctx.seconds)
    done = []
    step = r.engine.step
    r.engine.step = lambda: done.extend(step()) or []
    r.window(ctx.seconds, ctx.traffic["drain_s"])
    out = drv.outputs_of(done)
    pick = drv.sample(r, out)
    gap = {lower: max(float(np.max(served_gaps(
        r.c, r.params, q.tokens, out[q.rid], lower=lower))) for q in pick)
        for lower in (False, True)}
    print("served gap", gap[False], "control gap", gap[True])
    assert gap[True] >= 3 * gap[False]


def test_traced_slice_is_loaded_and_holds_no_drain():
    """A traced run of the overload cell profiles a slice that ends before
    arrivals stop, after the engine has filled its slots, and counts only
    the ticks of that slice."""
    import tracing
    ctx = small_ctx(OVERLOAD, trace=True)
    ctx.seconds = 4.0
    drv = driver(ctx)
    drv.TRACE_SECONDS, drv.TRACE_END_BEFORE = 1.5, 1.0
    r = drv.Run(ctx.conf, ctx.traffic, ctx.seed, ctx.limits)
    r.setup(ctx.seconds)
    r.window(ctx.seconds, ctx.traffic["drain_s"],
             tracing.Tracer(ctx.trace_dir))
    k_on, k_off = r.traced
    ends = r.rec.tick_end
    assert 0 < k_on < k_off <= len(ends)
    assert ends[k_on - 1] >= ctx.seconds - 2.5
    assert ends[k_off - 1] <= ctx.seconds
    assert 0 < r.window_s < ctx.seconds
    c = r.counts()
    assert c["model_flops"] > 0 and c["qd_bytes"] > 0
    # ticks outside the slice add nothing
    r.traced = (k_on, k_on)
    assert r.counts()["qd_bytes"] == 0
