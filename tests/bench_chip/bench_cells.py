"""Small CPU versions of the benchmark's cells, for the tests in this
directory: a whole run of a cell through its driver (the harness's look
for a chip is skipped, everything else is the run's own path), judged by
the cell's committed limits."""
from __future__ import annotations

import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import harness  # noqa: E402

TRAIN = "train.whisper-tiny.n8c8q4"
SERVE = "serve.deepseek-67b-4l.steady"
OVERLOAD = "serve.deepseek-67b-4l.overload"


def small_ctx(cell, dtype=None, fault=None, seed=2 ** 35 + 11, trace=False):
    import jax
    manifest, w = harness.cell(cell)
    conf = harness.load_json("configs", w["config"])
    traffic = harness.load_json("traffic", w["traffic"])
    m = conf["model"]
    if m["family"] == "encdec":
        m.update(d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
                 d_ff=256, vocab=2048, n_layers=2, encoder_layers=2)
        traffic.update(population=4, cohort=2, q=2, seq=64)
        seconds = 1.0
    else:
        m.update(d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
                 d_ff=512, vocab=4096, n_layers=2)
        conf["serve"].update(slots=4, max_len=160)
        traffic.update(prompt_lens=[8, 16, 32, 64],
                       rate=20.0 * traffic["rate"] / 3.2,
                       mean_new_tokens=16, max_new_cap=64, drain_s=30)
        seconds = 2.0
    if dtype:
        m["dtype"] = dtype
    return SimpleNamespace(
        manifest=manifest, cell=w, conf=conf, traffic=traffic,
        limits=harness.load_json("limits", cell), seed=seed, seconds=seconds,
        trace=trace, t_start=time.perf_counter(), devs=jax.devices(),
        fault=fault, trace_dir=ROOT / ".bench_trace")


def driver(ctx):
    return harness.load_module(CHIP / "drivers" / f"{ctx.conf['driver']}.py")


def run_cell(ctx):
    """The run's ``correct``, with its checks printed."""
    out = driver(ctx).run(ctx)
    print("\n".join(out["checks"].lines()))
    return out["checks"].correct
