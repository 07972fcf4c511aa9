"""Telemetry subsystem (repro.obs): record schema, progress formats, the
strictly-observational parity guarantee, on-device stat accumulation,
profiler annotations, and the async round-timing fence.

The two load-bearing pins:

  * **parity** — attaching a live Telemetry bus (sinks + StatAccum) to a
    FedDriver run must leave the trajectory BIT-identical on all four
    engines: the stats are computed by a separate jitted program on each
    round's output states, never folded into the round programs.
  * **fence** — the async engine's per-round wall-clock must measure
    completion, not dispatch: a forced sleep inside the round program
    lower-bounds every recorded round time.
"""
import json
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PopulationConfig
from repro.obs import (JsonlSink, MemorySink, StatAccum, Telemetry,
                       progress_line, run_manifest)
from repro.obs.telemetry import SCHEMA
from repro.tasks.driver import FedDriver

sys.path.insert(0, ".")
from tests.test_system import _quad_driver  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


# ------------------------------------------------------------------ manifest

def test_manifest_fields():
    man = run_manifest(config={"steps": 5, "arch": "x"}, seed=7,
                       extra_field="v")
    assert man["kind"] == "manifest"
    assert man["schema"] == SCHEMA
    for k in ("run_id", "created", "argv", "host", "python", "jax_version",
              "platform", "device_count", "devices", "git_sha", "seed"):
        assert k in man, k
    assert man["seed"] == 7
    assert man["config"]["steps"] == 5
    assert man["extra_field"] == "v"
    assert man["jax_version"] == jax.__version__
    assert man["device_count"] == len(jax.devices())
    # the manifest must be JSON-encodable as-is (sinks json.dumps it)
    json.dumps(man)


def test_manifest_emitted_first_and_flushed():
    sink = MemorySink()
    tele = Telemetry([sink], metrics_every=4)
    tele.manifest(config={"a": 1}, seed=0)
    # manifest flushes immediately — no waiting for the round cadence
    assert sink.records and sink.records[0]["kind"] == "manifest"
    tele.close()


def test_round_buffering_flush_cadence():
    sink = MemorySink()
    tele = Telemetry([sink], metrics_every=3)
    tele.round(0, round_seconds=0.1)
    tele.round(1, round_seconds=0.1)
    assert sink.of_kind("round") == []          # buffered, not yet flushed
    tele.round(2, round_seconds=0.1)
    assert len(sink.of_kind("round")) == 3      # flushed at the window
    tele.close()
    summary = sink.of_kind("summary")
    assert len(summary) == 1 and summary[0]["rounds"] == 3


def test_metrics_every_validation():
    with pytest.raises(ValueError):
        Telemetry([], metrics_every=0)


# ------------------------------------------------------------------ progress

def test_progress_line_eager_format():
    # the legacy eager per-step print, character for character
    loss, el, t = 0.123456, 4.25, 7
    assert (progress_line(loss=loss, elapsed=el, step=t)
            == f"step {t:5d}  f(x̄,ȳ) = {loss:.4f}  ({el:.1f}s)")


def test_progress_line_scan_format():
    loss, el, t, r, dt = 5.0 / 3, 12.04, 47, 11, 0.01234
    assert (progress_line(loss=loss, elapsed=el, step=t, round=r,
                          round_seconds=dt)
            == f"round {r:4d} (step {t:5d})  f(x̄,ȳ) = {loss:.4f}  "
               f"round={dt*1e3:.1f}ms  ({el:.1f}s)")


def test_progress_line_population_format():
    loss, el, t, r, dt = 2.5, 100.0, 39, 4, 0.5
    up, dn = 37_850_000, 151_390_000
    ids = [7, 4, 1, 0, 2, 9, 8, 3, 6, 5]       # > 8 ids: truncated display
    assert (progress_line(loss=loss, elapsed=el, step=t, round=r,
                          round_seconds=dt, bytes_up=up, bytes_down=dn,
                          cohort=ids)
            == f"round {r:4d} (step {t:5d})  f(x̄,ȳ) = {loss:.4f}  "
               f"round={dt*1e3:.1f}ms  "
               f"up={up/1e6:.2f}MB down={dn/1e6:.2f}MB  "
               f"cohort={ids[:8]}...  ({el:.1f}s)")


def test_progress_line_async_format():
    loss, el, t, r, dt = 0.9, 3.3, 15, 3, 0.002
    assert (progress_line(loss=loss, elapsed=el, step=t, round=r,
                          round_seconds=dt, arrived=3, dropped=1,
                          mean_staleness=1.5, eta_scale=0.87,
                          bytes_up=1_000_000, bytes_down=2_000_000,
                          cohort=[0, 1])
            == f"round {r:4d} (step {t:5d})  f(x̄,ȳ) = {loss:.4f}  "
               f"round={dt*1e3:.1f}ms  "
               f"arrived=3 dropped=1 tau=1.50 eta_scale=0.870  "
               f"up=1.00MB down=2.00MB  cohort=[0, 1]...  ({el:.1f}s)")


# ------------------------------------------------------------------ devstats

def test_statacc_update_norm_and_ring():
    states = {"x": jnp.ones((4, 3)), "y": jnp.zeros((4, 2))}
    acc = StatAccum.create(states, k=3)
    for _ in range(3):
        acc.update(states)                      # identical states each round
    assert acc.ready
    out = acc.drain()
    assert out["round_start"] == 0
    assert len(out["global_norm"]) == 3
    # avg state is (ones(3), zeros(2)) -> global norm sqrt(3)
    assert out["global_norm"] == pytest.approx([3.0 ** 0.5] * 3)
    # the mean state never moves -> update norm 0 every round
    assert out["update_norm"] == pytest.approx([0.0] * 3, abs=1e-7)
    # partial tail window: round_start advances past the drained rows
    acc.update(jax.tree.map(lambda a: a * 2.0, states))
    assert not acc.ready and acc.pending == 1
    tail = acc.drain()
    assert tail["round_start"] == 3
    assert len(tail["update_norm"]) == 1
    assert tail["update_norm"][0] > 0.0         # the mean moved this time


def test_statacc_consensus_zero_for_identical_rows():
    states = {"x": jnp.ones((5, 2)) * 3.0}
    acc = StatAccum.create(states, k=2, consensus=True)
    assert acc.fields == ("global_norm", "update_norm", "consensus")
    acc.update(states)
    out = acc.drain()
    assert out["consensus"] == pytest.approx([0.0], abs=1e-7)


# ------------------------------------------------------------------ parity

def _result_tuple(r):
    return (r.steps, r.samples, r.comms, r.bytes_up, r.bytes_down)


def _engines():
    yield "eager", {}
    yield "scan", {}
    yield "population", {"population": PopulationConfig(n=8, cohort=2)}
    yield "async", {"population": PopulationConfig(
        n=8, cohort=2, max_staleness=4.0, max_delay=2)}


@pytest.mark.parametrize("name,cfg", list(_engines()))
def test_telemetry_parity_bit_identical(name, cfg):
    """Attaching a live bus (sink + on-device StatAccum) never changes the
    trajectory: every counter and every float of the run is IDENTICAL."""
    def run(with_tele):
        d = _quad_driver("adafbio", m=8)
        if "population" in cfg:
            d.population = cfg["population"]
        elif name == "scan":
            d.engine = "scan"
        tele = None
        if with_tele:
            tele = Telemetry([MemorySink()], metrics_every=2)
            d.telemetry = tele
        r = d.run(12, key=jax.random.PRNGKey(0), eval_every=4)
        if tele is not None:
            tele.close()
        return r, tele

    r_off, _ = run(False)
    r_on, tele = run(True)
    assert _result_tuple(r_on) == _result_tuple(r_off)
    # grad_norm is exact; metric may be NaN (no metric_fn on the quad task)
    assert np.array_equal(np.asarray(r_on.grad_norm),
                          np.asarray(r_off.grad_norm))
    assert np.array_equal(np.asarray(r_on.metric), np.asarray(r_off.metric),
                          equal_nan=True)
    for a, b in zip(jax.tree.leaves(r_on.final_avg_state),
                    jax.tree.leaves(r_off.final_avg_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the instrumented run actually recorded its rounds + stats
    sink = tele.sinks[0]
    rounds = sink.of_kind("round")
    assert len(rounds) == 3
    assert [r["round"] for r in rounds] == [0, 1, 2]
    stats = sink.of_kind("stats")
    assert stats and all(len(s["update_norm"]) >= 1 for s in stats)
    assert sum(len(s["update_norm"]) for s in stats) == 3


@pytest.mark.parametrize("name,cfg",
                         [(n, c) for n, c in _engines() if n != "eager"])
def test_megascan_telemetry_parity_bit_identical(name, cfg):
    """rounds_per_scan > 1 folds the stat rows INTO the mega program as
    unconditional scan outputs — telemetry on and off run the SAME
    compiled bytes. The trajectory and every counter stay bit-identical,
    and the bus still sees one round record per round and one stats row
    per round, drained once per chunk."""
    def run(with_tele):
        d = _quad_driver("adafbio", m=8)
        d.rounds_per_scan = 3
        if "population" in cfg:
            d.population = cfg["population"]
        else:
            d.engine = "scan"
        tele = None
        if with_tele:
            tele = Telemetry([MemorySink()], metrics_every=2)
            d.telemetry = tele
        r = d.run(12, key=jax.random.PRNGKey(0), eval_every=4)
        if tele is not None:
            tele.close()
        return r, tele

    r_off, _ = run(False)
    r_on, tele = run(True)
    assert _result_tuple(r_on) == _result_tuple(r_off)
    assert np.array_equal(np.asarray(r_on.grad_norm),
                          np.asarray(r_off.grad_norm))
    for a, b in zip(jax.tree.leaves(r_on.final_avg_state),
                    jax.tree.leaves(r_off.final_avg_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    sink = tele.sinks[0]
    rounds = sink.of_kind("round")
    assert [rec["round"] for rec in rounds] == [0, 1, 2]
    stats = sink.of_kind("stats")
    assert stats and sum(len(s["update_norm"]) for s in stats) == 3
    starts = [s["round_start"] for s in stats]
    assert starts == sorted(starts)


def test_megascan_rejects_consensus_stat():
    """The O(N) consensus stat reads pre-sync states mid-round and cannot
    fold into the chunked program — asking for both is a loud error, not a
    silent drop."""
    d = _quad_driver("adafbio", m=8)
    d.engine = "scan"
    d.rounds_per_scan = 2
    d.telemetry = Telemetry([MemorySink()], metrics_every=2,
                            consensus=True)
    with pytest.raises(ValueError, match="consensus"):
        d.run(12, key=jax.random.PRNGKey(0), eval_every=4)


# ------------------------------------------------------------------ stream

def test_jsonl_roundtrip_and_report_check(tmp_path):
    out = tmp_path / "run.jsonl"
    d = _quad_driver("adafbio", m=8)
    d.population = PopulationConfig(n=8, cohort=2)
    tele = Telemetry([JsonlSink(str(out))], metrics_every=2)
    d.telemetry = tele
    tele.manifest(config={"task": "quad"}, seed=0)
    d.run(12, key=jax.random.PRNGKey(0), eval_every=4)
    tele.close()

    records = [json.loads(line) for line in out.read_text().splitlines()]
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "manifest"
    assert kinds.count("round") == 3
    assert kinds.count("summary") == 1
    assert kinds[-1] == "summary"
    summary = records[-1]
    assert summary["rounds"] == 3
    assert "round_program" in summary["phases"]
    assert summary["phases"]["round_program"]["count"] == 3

    # scripts/report.py validates and renders the same stream (the CI gate)
    chk = subprocess.run([sys.executable, "scripts/report.py", str(out),
                          "--check"], cwd=ROOT, capture_output=True,
                         text=True)
    assert chk.returncode == 0, chk.stderr
    assert "report: OK" in chk.stdout
    ren = subprocess.run([sys.executable, "scripts/report.py", str(out)],
                         cwd=ROOT, capture_output=True, text=True)
    assert ren.returncode == 0, ren.stderr
    assert "rounds: 3" in ren.stdout
    assert "phase breakdown" in ren.stdout


def test_jsonl_chunked_drain_report_check(tmp_path):
    """A mega-scan run's stream — round records emitted per round but
    drained once per chunk, stats rows stacked per chunk — still satisfies
    every scripts/report.py --check invariant (ordered rounds, equal-length
    stat columns, summary.rounds == #round records)."""
    out = tmp_path / "mega.jsonl"
    d = _quad_driver("adafbio", m=8)
    d.rounds_per_scan = 3
    d.population = PopulationConfig(n=8, cohort=2)
    tele = Telemetry([JsonlSink(str(out))], metrics_every=2)
    d.telemetry = tele
    tele.manifest(config={"task": "quad", "rounds_per_scan": 3}, seed=0)
    d.run(20, key=jax.random.PRNGKey(0), eval_every=4)  # 5 rounds: 1+3+1
    tele.close()

    records = [json.loads(line) for line in out.read_text().splitlines()]
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "manifest"
    assert kinds.count("round") == 5
    assert [r["round"] for r in records if r["kind"] == "round"] == list(
        range(5))
    assert records[-1]["rounds"] == 5
    stats = [r for r in records if r["kind"] == "stats"]
    assert sum(len(s["update_norm"]) for s in stats) == 5

    chk = subprocess.run([sys.executable, "scripts/report.py", str(out),
                          "--check"], cwd=ROOT, capture_output=True,
                         text=True)
    assert chk.returncode == 0, chk.stderr
    assert "report: OK" in chk.stdout


def test_phase_self_time_on_nested_spans(tmp_path, monkeypatch):
    """A span's ``self_seconds`` is its time less its child spans', so the
    self times of a tree add up to its root's time once; report.py shows
    both columns."""
    from repro.obs import telemetry
    out = tmp_path / "nested.jsonl"
    tele = Telemetry([JsonlSink(str(out))])
    tele.manifest(config={}, seed=0)
    clock = iter([0.0, 1.0, 4.0, 5.0, 6.0, 8.0, 10.0, 12.0])
    monkeypatch.setattr(telemetry.time, "perf_counter", lambda: next(clock))
    with tele.span("outer"):            # 0 .. 12
        with tele.span("inner"):        # 1 .. 4
            pass
        with tele.span("inner"):        # 5 .. 10
            with tele.span("leaf"):     # 6 .. 8
                pass
    monkeypatch.undo()
    assert tele.summary()["phases"] == {
        "inner": {"count": 2, "seconds": 8.0, "self_seconds": 6.0},
        "leaf": {"count": 1, "seconds": 2.0, "self_seconds": 2.0},
        "outer": {"count": 1, "seconds": 12.0, "self_seconds": 4.0}}
    tele.close()
    ren = subprocess.run([sys.executable, "scripts/report.py", str(out)],
                         cwd=ROOT, capture_output=True, text=True)
    assert ren.returncode == 0, ren.stderr
    line = next(x for x in ren.stdout.splitlines() if "outer" in x).split()
    assert line[1:3] == ["12000.0ms", "4000.0ms"] and line[-1] == "33.3%"


def test_report_check_rejects_malformed_stream(tmp_path):
    bad = tmp_path / "bad.jsonl"
    # no manifest, unknown kind, stats with ragged columns
    bad.write_text(json.dumps({"kind": "round", "round": 1}) + "\n"
                   + json.dumps({"kind": "nonsense"}) + "\n"
                   + json.dumps({"kind": "stats", "round_start": 0,
                                 "a": [1.0, 2.0], "b": [1.0]}) + "\n")
    chk = subprocess.run([sys.executable, "scripts/report.py", str(bad),
                          "--check"], cwd=ROOT, capture_output=True,
                         text=True)
    assert chk.returncode == 1
    assert "manifest" in chk.stderr
    assert "unknown kind" in chk.stderr
    assert "unequal" in chk.stderr


# ------------------------------------------------------------------ profiler

@pytest.mark.slow
def test_profile_trace_contains_named_regions(tmp_path):
    """--profile produces a TensorBoard-loadable trace whose raw bytes
    contain the span names (host TraceAnnotations) and the round/* named
    scopes (XLA op metadata)."""
    d = _quad_driver("adafbio", m=8)
    d.population = PopulationConfig(n=8, cohort=2)
    tele = Telemetry([], metrics_every=4, profile_dir=str(tmp_path))
    d.telemetry = tele
    d.run(8, key=jax.random.PRNGKey(0), eval_every=4)
    tele.close()
    traces = list(tmp_path.rglob("*.xplane.pb"))
    assert traces, "no xplane trace written"
    blob = b"".join(t.read_bytes() for t in traces)
    for name in (b"round_program", b"batch_build", b"round/gather",
                 b"round/local_scan", b"round/aggregate", b"round/scatter"):
        assert name in blob, f"annotation {name!r} missing from trace"


# ------------------------------------------------------------------ fence

def test_async_round_timing_forced_sleep(monkeypatch):
    """The async engine fences (block_until_ready) inside its round timer:
    a sleep injected INTO the jitted round program must show up in every
    recorded round time. Without the fence, dispatch returns immediately
    and the recorded times would be ~0."""
    SLEEP = 0.05
    orig = FedDriver._cohort_local_step

    def slowed(self, n):
        step = orig(self, n)

        def nap(t):
            time.sleep(SLEEP)
            return np.asarray(t)

        def slow_step(states, srv, batch, kk, ids):
            states, srv = step(states, srv, batch, kk, ids)
            srv = dict(srv)
            # thread the sleep through the live carry so it cannot be
            # dead-code-eliminated; runs once per local step
            srv["t"] = jax.pure_callback(
                nap, jax.ShapeDtypeStruct(jnp.shape(srv["t"]),
                                          jnp.result_type(srv["t"])),
                srv["t"])
            return states, srv
        return slow_step

    monkeypatch.setattr(FedDriver, "_cohort_local_step", slowed)
    d = _quad_driver("adafbio", m=8)
    d.population = PopulationConfig(n=8, cohort=2, max_staleness=4.0,
                                    max_delay=2)
    q = d.fed.q
    r = d.run(3 * q, key=jax.random.PRNGKey(0), eval_every=100)
    # every round runs q local steps -> >= q * SLEEP of forced wall-clock
    floor = q * SLEEP * 0.9
    assert r.compile_seconds >= floor, r.compile_seconds
    assert len(d.round_seconds) == 2
    for dt in d.round_seconds:
        assert dt >= floor, (dt, d.round_seconds)
