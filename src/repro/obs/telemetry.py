"""Unified telemetry bus: structured run metrics, phase spans, profiler hooks.

Every run artifact the repo produces — the per-round progress lines of
``launch/train.py``, the driver's ``RunResult`` counters, the BENCH JSONs —
tracks the inputs of the paper's headline cost curves (samples, comms,
bytes on the wire, staleness). This module gives them ONE schema-versioned
stream instead of ad-hoc lists and print blocks:

  * a :class:`Telemetry` bus with pluggable sinks (:class:`JsonlSink`,
    :class:`MemorySink`) emitting a run **manifest**
    (:func:`run_manifest`: config, git SHA, jax version, device topology,
    seed) followed by per-round ``round`` records, device-drained ``stats``
    records and a closing ``summary``;
  * :meth:`Telemetry.span` phase timers — the caller fences with
    ``jax.block_until_ready`` (or :meth:`Span.fence`) INSIDE the span so the
    timer measures completion, not dispatch — that double as
    ``jax.profiler.TraceAnnotation`` regions, so gather / round-program /
    scatter / spill-prefetch show up as named regions in a profiler trace;
  * profiler hooks: ``Telemetry(profile_dir=...)`` starts a
    ``jax.profiler`` trace (TensorBoard-viewable) and stops it at
    :meth:`close`.

Record kinds (one JSON object per line in a metrics JSONL):

  manifest   first record of every stream; ``schema`` = :data:`SCHEMA`
  round      one per communication round: ``round``, ``step``,
             ``round_seconds``, cumulative ``samples``/``comms``/
             ``bytes_up``/``bytes_down``, engine extras (async arrival
             stats), buffered and flushed every ``metrics_every`` rounds
  stats      a drained on-device accumulator window
             (``repro.obs.devstats``): ``round_start`` + one list per
             scalar field, one host transfer per ``metrics_every`` rounds
  summary    aggregates at close: steady rounds/sec, phase span totals
             (``seconds`` and ``self_seconds``, the time outside child
             spans), wire totals, staleness histogram when the run
             recorded one

``scripts/report.py`` renders (or ``--check`` validates) any such stream;
the schema spec lives in docs/observability.md. Telemetry is strictly
observational: enabling it never changes a trajectory
(tests/test_obs.py pins bit-identical ``RunResult`` across all four
engines).
"""
from __future__ import annotations

import dataclasses
import json
import os
import platform
import subprocess
import sys
import time
import uuid
from typing import Any, Dict, List, Optional

# bumped whenever a record kind gains/changes a required field
SCHEMA = 1

KINDS = ("manifest", "round", "stats", "summary", "bench_row",
         "request", "tick")


# ------------------------------------------------------------------ sinks

class JsonlSink:
    """Append records to a JSONL file, one JSON object per line."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "w")

    def write(self, record: Dict[str, Any]) -> None:
        self._f.write(json.dumps(record) + "\n")

    def flush(self) -> None:
        self._f.flush()

    def close(self) -> None:
        self._f.close()


class MemorySink:
    """Keep records in a list — the test/driver-embedding sink."""

    def __init__(self):
        self.records: List[Dict[str, Any]] = []

    def write(self, record: Dict[str, Any]) -> None:
        self.records.append(record)

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass

    def of_kind(self, kind: str) -> List[Dict[str, Any]]:
        return [r for r in self.records if r.get("kind") == kind]


# ------------------------------------------------------------------ manifest

def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=5,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def _json_safe(x):
    """Best-effort conversion of config values to JSON-encodable types."""
    if isinstance(x, dict):
        return {str(k): _json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_json_safe(v) for v in x]
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return repr(x)


def run_manifest(config: Optional[Dict[str, Any]] = None,
                 seed: Optional[int] = None, **extra) -> Dict[str, Any]:
    """The schema-versioned run manifest: everything needed to know WHAT
    produced a metrics stream — config, git SHA, jax version, device
    topology, seed. Emitted as the first record of every telemetry stream
    and embedded as the ``manifest`` header of the BENCH JSON artifacts."""
    import jax
    devices = jax.devices()
    mesh = extra.pop("mesh", None)
    man = {
        "kind": "manifest",
        "schema": SCHEMA,
        "run_id": uuid.uuid4().hex[:12],
        "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "argv": list(sys.argv),
        "host": platform.node(),
        "python": platform.python_version(),
        "jax_version": jax.__version__,
        "platform": devices[0].platform if devices else "none",
        "device_count": len(devices),
        "devices": [str(d) for d in devices[:16]],
        "mesh": (dict(zip(mesh.axis_names, map(int, mesh.devices.shape)))
                 if mesh is not None else None),
        "git_sha": _git_sha(),
        "seed": seed,
        "config": _json_safe(config) if config is not None else None,
    }
    man.update(_json_safe(extra))
    return man


# ------------------------------------------------------------------ spans

class Span:
    """One timed phase region: wall-clock via ``perf_counter`` plus a
    ``jax.profiler.TraceAnnotation`` so the phase shows up as a named
    region in a profiler trace. Fence async work INSIDE the span (either
    explicitly or via :meth:`fence`) so the timer measures completion, not
    dispatch. Spans nest: each adds its time to the enclosing open span's
    ``_child``, which that span's self time leaves out."""

    __slots__ = ("name", "_tele", "_ann", "_t0", "_child")

    def __init__(self, name: str, tele: "Telemetry"):
        self.name = name
        self._tele = tele
        self._child = 0.0

    def fence(self, x):
        """``jax.block_until_ready`` passthrough — the phase ends when the
        device work it dispatched is DONE."""
        import jax
        return jax.block_until_ready(x)

    def __enter__(self) -> "Span":
        import jax
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._tele._open.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._tele._close_span(self, dt)
        return False


class _NullSpan:
    """Reusable no-op span for the disabled-telemetry path."""

    __slots__ = ()

    def fence(self, x):
        import jax
        return jax.block_until_ready(x)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


# ------------------------------------------------------------------ the bus

class Telemetry:
    """The telemetry bus: buffers records, aggregates round/phase totals,
    flushes to every sink each ``metrics_every`` rounds and at close.

    ``metrics_every`` is both the flush cadence AND the on-device stat
    drain window the drivers use (``repro.obs.devstats``); ``consensus``
    asks the device accumulator for the (O(N) compute) consensus-error
    scalar as well. ``profile_dir`` starts a ``jax.profiler`` trace
    immediately and stops it at :meth:`close` — load with
    ``tensorboard --logdir <dir>``."""

    def __init__(self, sinks=(), metrics_every: int = 8,
                 profile_dir: Optional[str] = None,
                 consensus: bool = False):
        if metrics_every < 1:
            raise ValueError(f"metrics_every must be >= 1 round, got "
                             f"{metrics_every}")
        self.sinks = list(sinks)
        self.metrics_every = metrics_every
        self.consensus = consensus
        self.profile_dir = profile_dir
        self._buf: List[Dict[str, Any]] = []
        # name -> [count, secs, self secs]
        self._phases: Dict[str, List[float]] = {}
        self._open: List[Span] = []     # spans entered and not yet exited
        self._rounds = 0
        self._round_seconds: List[float] = []
        self._last: Dict[str, Any] = {}
        self._notes: Dict[str, Any] = {}
        self._closed = False
        self._profiling = False
        if profile_dir:
            import jax
            os.makedirs(profile_dir, exist_ok=True)
            jax.profiler.start_trace(profile_dir)
            self._profiling = True

    # ------------------------------------------------------------ records

    def emit(self, record: Dict[str, Any]) -> None:
        self._buf.append(record)

    def manifest(self, config=None, seed=None, **extra) -> Dict[str, Any]:
        man = run_manifest(config, seed, **extra)
        self.emit(man)
        self.flush()
        return man

    def round(self, round: int, **fields) -> None:
        """One per-round record; buffered, flushed every ``metrics_every``
        rounds. Cumulative counters (``samples``/``comms``/``bytes_up``/
        ``bytes_down``) are remembered for the closing summary."""
        rec = {"kind": "round", "round": int(round)}
        rec.update(fields)
        self.emit(rec)
        self._rounds += 1
        if "round_seconds" in fields:
            self._round_seconds.append(float(fields["round_seconds"]))
        for k in ("samples", "comms", "bytes_up", "bytes_down", "step"):
            if k in fields:
                self._last[k] = fields[k]
        if self._rounds % self.metrics_every == 0:
            self.flush()

    def stats(self, round_start: int, **columns) -> None:
        """A drained on-device accumulator window: ``round_start`` plus one
        equal-length list per scalar field (``repro.obs.devstats``)."""
        rec = {"kind": "stats", "round_start": int(round_start)}
        rec.update({k: [float(v) for v in vs] for k, vs in columns.items()})
        self.emit(rec)

    def note(self, **kw) -> None:
        """Stash extra fields (e.g. the final staleness histogram) into the
        closing summary record."""
        self._notes.update(kw)

    # ------------------------------------------------------------ serving

    def request(self, rid: int, **fields) -> None:
        """One completed serve request (``repro.serve.engine``): prompt/new
        token counts, finish reason, latency. Buffered like rounds."""
        rec = {"kind": "request", "rid": int(rid)}
        rec.update(_json_safe(fields))
        self.emit(rec)

    def tick(self, tick: int, **fields) -> None:
        """One engine scheduler tick (slot occupancy, admissions,
        completions); flushed every ``metrics_every`` ticks."""
        rec = {"kind": "tick", "tick": int(tick)}
        rec.update(_json_safe(fields))
        self.emit(rec)
        if self.sinks and (tick + 1) % self.metrics_every == 0:
            self.flush()

    # ------------------------------------------------------------ spans

    def span(self, name: str) -> Span:
        return Span(name, self)

    def _close_span(self, span: Span, dt: float) -> None:
        self._open.pop()
        if self._open:
            self._open[-1]._child += dt
        agg = self._phases.setdefault(span.name, [0, 0.0, 0.0])
        agg[0] += 1
        agg[1] += dt
        agg[2] += dt - span._child

    @property
    def phase_totals(self) -> Dict[str, Dict[str, float]]:
        return {k: {"count": int(c), "seconds": round(s, 6),
                    "self_seconds": round(own, 6)}
                for k, (c, s, own) in sorted(self._phases.items())}

    # ------------------------------------------------------------ lifecycle

    def flush(self) -> None:
        for rec in self._buf:
            for s in self.sinks:
                s.write(rec)
        self._buf.clear()
        for s in self.sinks:
            s.flush()

    def summary(self) -> Dict[str, Any]:
        # steady-state excludes the first recorded round — it carries the
        # compile (the drivers' RunResult.compile_seconds convention)
        steady = self._round_seconds[1:] or self._round_seconds
        per = sum(steady) / len(steady) if steady else None
        rec = {"kind": "summary",
               "rounds": self._rounds,
               "round_seconds_mean": (round(per, 6)
                                      if per is not None else None),
               "rounds_per_sec": (round(1.0 / per, 3)
                                  if per else None),
               "phases": self.phase_totals}
        rec.update({k: v for k, v in self._last.items()})
        rec.update(_json_safe(self._notes))
        return rec

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._rounds or self._phases or self._notes:
            self.emit(self.summary())
        self.flush()
        for s in self.sinks:
            s.close()
        if self._profiling:
            import jax
            jax.profiler.stop_trace()
            self._profiling = False


class NullTelemetry:
    """Do-nothing stand-in so instrumented call sites never branch; spans
    are reusable no-ops (still usable as fences)."""

    sinks = ()
    metrics_every = 0
    consensus = False

    def emit(self, record) -> None:
        pass

    def manifest(self, config=None, seed=None, **extra):
        return None

    def round(self, round, **fields) -> None:
        pass

    def stats(self, round_start, **columns) -> None:
        pass

    def note(self, **kw) -> None:
        pass

    def request(self, rid, **fields) -> None:
        pass

    def tick(self, tick, **fields) -> None:
        pass

    def span(self, name):
        return _NULL_SPAN

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL = NullTelemetry()


def make_telemetry(metrics_out: Optional[str] = None,
                   metrics_every: int = 8,
                   profile_dir: Optional[str] = None,
                   consensus: bool = False) -> Telemetry:
    """The launcher-facing constructor: a JSONL sink when ``metrics_out``
    is set, profiling when ``profile_dir`` is set. With nothing enabled
    the bus still aggregates spans (so phase totals can be printed) at
    negligible cost."""
    sinks = [JsonlSink(metrics_out)] if metrics_out else []
    return Telemetry(sinks, metrics_every=metrics_every,
                     profile_dir=profile_dir, consensus=consensus)
