"""Profiler traces: capture a steady window, flatten it to plain events, and
reduce the events to device time.

``capture`` wraps a callable in ``jax.profiler`` and returns the window's
events as dicts (``plane``, ``line``, ``name``, ``start_ns``, ``dur_ns``,
``stats``), device planes and the benchmark's own host spans only. The
reduction below works on that list, so a small recorded list under
``testdata/`` checks it on the CPU.

Device time is read from the ``XLA Ops`` line of each ``/device:`` plane.
An op belongs to a named scope when its name stack holds the scope; a
kernel is found by its name. On a v5e the trace's op events carry neither:
their name is the HLO instruction. So ``annotate`` reads both from the
compiled programs' HLO text, keyed by program and instruction name: the
name stack from each instruction's ``op_name`` metadata (as ``tf_op``), and
for a Pallas kernel the identifiers in its serialised Mosaic body (as
``kernel``), which hold the kernel function's name and source path.
"""
from __future__ import annotations

import base64
import glob
import gzip
import json
import re
import shutil
import time
from pathlib import Path

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def _events_from_xplane(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                if not device and not e.name.startswith(HOST_PREFIX):
                    continue
                stats = {}
                if device:
                    for k, v in e.stats:
                        stats[str(k)] = str(v)[:400]
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "start_ns": int(e.start_ns),
                            "dur_ns": int(e.duration_ns), "stats": stats})
    return out


class Tracer:
    """``jax.profiler`` over a slice of a run: ``start()`` and ``stop()``
    may be called from inside the loop being traced; ``stop`` returns
    (events, window_s). The trace files are deleted afterwards."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)
        self.t0 = self.span = None

    def start(self):
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.out_dir))
        self.span = jax.profiler.TraceAnnotation(HOST_PREFIX + "window")
        self.span.__enter__()
        self.t0 = time.perf_counter()

    @property
    def started(self):
        return self.t0 is not None

    def stop(self):
        import jax
        window_s = time.perf_counter() - self.t0
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        events = _events_from_xplane(paths[0]) if paths else []
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return events, window_s


def capture(fn, out_dir: Path):
    """Run ``fn()`` under the profiler; returns (fn's value, events,
    window_s)."""
    tr = Tracer(out_dir)
    tr.start()
    try:
        value = fn()
    finally:
        events, window_s = tr.stop()
    return value, events, window_s


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_BODY = re.compile(r'"body":"([^"]*)"')
_IDENT = re.compile(rb"[A-Za-z_][A-Za-z0-9_]{3,}")


def hlo_index(hlo_text):
    """(module name, {instruction: {"tf_op": .., "kernel": ..}}) from one
    compiled program's HLO text."""
    module = hlo_text.split(",", 1)[0].split()[-1] if hlo_text else ""
    index = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        info = {}
        op = _OP_NAME.search(line)
        if op:
            info["tf_op"] = op.group(1)
        body = _BODY.search(line)
        if body:
            raw = base64.b64decode(body.group(1))
            info["kernel"] = "/".join(
                sorted({x.decode() for x in _IDENT.findall(raw)}))
        if info:
            index[m.group(1)] = info
    return module, index


def annotate(events, indexes):
    """Give each device op the name stack and kernel of its instruction,
    looked up in ``indexes`` ({module: {instruction: info}}) under the
    program whose ``XLA Modules`` event holds the op in time."""
    mods = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                   e["name"].split("(")[0], e["plane"]) for e in events
                  if e["line"] == "XLA Modules")
    for e in device_ops(events):
        instr = e["name"].split(" = ", 1)[0].lstrip("%")
        mid = e["start_ns"] + e["dur_ns"] // 2
        for s, t, name, plane in mods:
            if plane == e["plane"] and s <= mid <= t:
                info = indexes.get(name, {}).get(instr)
                if info:
                    for k, v in info.items():
                        e["stats"].setdefault(k, v)
                break
    return events


def save_events(obj, path):
    with gzip.open(path, "wt") as f:
        json.dump(obj, f)


def load_events(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_ops(events):
    return [e for e in events
            if e["plane"].startswith("/device:") and e["line"] == OPS_LINE]


def host_window(events):
    """(start_ns, end_ns) of the benchmark's ``bench.window`` span."""
    for e in events:
        if e["name"] == HOST_PREFIX + "window":
            return e["start_ns"], e["start_ns"] + e["dur_ns"]
    return None


def union_ns(intervals):
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(events):
    """Seconds in which some op ran, averaged over the device planes."""
    planes = {}
    for e in device_ops(events):
        planes.setdefault(e["plane"], []).append(
            (e["start_ns"], e["start_ns"] + e["dur_ns"]))
    if not planes:
        return 0.0
    return sum(union_ns(v) for v in planes.values()) / len(planes) / 1e9


def with_self_time(events):
    """Device ops, each with ``self_ns``: its duration less that of the
    ops nested in it (a loop's op event spans the ops of its body, on the
    same line), so that sums over ops count each nanosecond once. An op
    with no name stack of its own (a loop that XLA's passes made, whose
    instruction carries no ``op_name``) takes its parent's, else that of
    its first nested op that has one."""
    ops = device_ops(events)
    by_plane = {}
    for e in ops:
        e["self_ns"] = e["dur_ns"]
        by_plane.setdefault(e["plane"], []).append(e)
    for evs in by_plane.values():
        evs.sort(key=lambda e: (e["start_ns"], -e["dur_ns"]))
        stack, orphans = [], []
        for e in evs:
            end = e["start_ns"] + e["dur_ns"]
            while stack and stack[-1]["start_ns"] + stack[-1]["dur_ns"] \
                    <= e["start_ns"]:
                stack.pop()
            parent = None
            if stack and end <= stack[-1]["start_ns"] + stack[-1]["dur_ns"]:
                parent = stack[-1]
                parent["self_ns"] -= e["dur_ns"]
            if not scope_of(e) and parent is not None and scope_of(parent):
                e["stats"]["tf_op"] = scope_of(parent)
            if parent is not None and not scope_of(parent) and scope_of(e):
                parent["stats"]["tf_op"] = scope_of(e)
            stack.append(e)
    return ops


def scope_of(e):
    return e["stats"].get("tf_op", "")


def in_scope(e, scope):
    return f"/{scope}/" in "/" + scope_of(e) + "/"


def scope_s(events, scope):
    """Device seconds of the ops under named scope ``scope`` (self time,
    so nested ops count once), summed over the device planes."""
    return sum(e["self_ns"] for e in with_self_time(events)
               if in_scope(e, scope)) / 1e9


def is_kernel(e, kernel):
    return kernel in e["name"] or any(kernel in v for v in e["stats"].values())


def kernel_events(events, kernel):
    return [e for e in device_ops(events) if is_kernel(e, kernel)]


def module_events(events, needle):
    """Events of the ``XLA Modules`` line whose program name holds
    ``needle`` (one event per run of a compiled program)."""
    return [e for e in events if e["plane"].startswith("/device:")
            and e["line"] == "XLA Modules" and needle in e["name"]]


def top_ops(events, n=10):
    """The n op names (name stacks where known) that took most device
    self time: [[name, seconds]]."""
    acc = {}
    for e in with_self_time(events):
        key = scope_of(e) or e["name"]
        acc[key] = acc.get(key, 0) + e["self_ns"]
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k[-120:], v / 1e9] for k, v in top]


def idle_gaps(events, n=10):
    """The n longest gaps between device ops, each named by the benchmark
    host span that covered the gap's middle ("" where none did)."""
    ops = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                 for e in device_ops(events))
    spans = [e for e in events if not e["plane"].startswith("/device:")
             and e["name"] != HOST_PREFIX + "window"]
    gaps, end = [], None
    for s, e in ops:
        if end is not None and s > end:
            gaps.append((s - end, end, s))
        end = e if end is None else max(end, e)
    gaps.sort(reverse=True)
    out = []
    for g, s, e in gaps[:n]:
        mid = (s + e) // 2
        names = [sp["name"] for sp in spans
                 if sp["start_ns"] <= mid < sp["start_ns"] + sp["dur_ns"]]
        out.append([names[-1] if names else "", g / 1e9])
    return out
