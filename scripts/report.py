#!/usr/bin/env python
"""Render (or validate) a telemetry metrics JSONL stream.

Usage:
  python scripts/report.py RUN.jsonl            # human-readable run summary
  python scripts/report.py RUN.jsonl --check    # schema validation, exit != 0
                                                # on a malformed stream

The stream is whatever ``--metrics-out`` wrote (``launch/train.py``,
``benchmarks/run.py``) or a ``repro.obs.JsonlSink`` captured from a
``FedDriver`` run: one manifest record, then round / stats / bench_row
records, then one summary (docs/observability.md has the schema spec).
The rendered report covers rounds/sec (steady state — the first round
carries the compile), the phase span breakdown, wire totals and the
staleness histogram when the run recorded one.

Stdlib-only on purpose: CI validates artifacts with it before upload, and
it must run anywhere the JSONL lands.
"""
from __future__ import annotations

import argparse
import json
import sys

KNOWN_KINDS = {"manifest", "round", "stats", "summary", "bench_row",
               "request", "tick"}

# fields every record of the kind must carry (schema 1)
REQUIRED = {
    "manifest": ("schema", "run_id", "jax_version", "platform",
                 "device_count", "git_sha", "seed", "argv"),
    "round": ("round",),
    "stats": ("round_start",),
    "summary": ("rounds", "phases"),
    "bench_row": ("name", "us_per_call"),
    "request": ("rid", "prompt_len", "new_tokens", "finish_reason"),
    "tick": ("tick", "active"),
}


def load(path):
    records = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                records.append((i, json.loads(line)))
            except json.JSONDecodeError as e:
                raise SystemExit(f"{path}:{i}: not valid JSON ({e})")
    if not records:
        raise SystemExit(f"{path}: empty stream")
    return records


def check(path, records):
    """Validate the stream; returns the list of problems (empty = OK)."""
    problems = []
    first = records[0][1]
    if first.get("kind") != "manifest":
        problems.append(f"line {records[0][0]}: first record must be the "
                        f"manifest, got kind={first.get('kind')!r}")
    for ln, rec in records:
        kind = rec.get("kind")
        if kind not in KNOWN_KINDS:
            problems.append(f"line {ln}: unknown kind {kind!r}")
            continue
        missing = [k for k in REQUIRED.get(kind, ()) if k not in rec]
        if missing:
            problems.append(f"line {ln}: {kind} record missing "
                            f"{missing}")
    rounds = [rec for _, rec in records if rec.get("kind") == "round"]
    ids = [r.get("round") for r in rounds if isinstance(r.get("round"), int)]
    if ids != sorted(ids):
        problems.append("round records out of order")
    ticks = [rec.get("tick") for _, rec in records
             if rec.get("kind") == "tick" and isinstance(rec.get("tick"), int)]
    if ticks != sorted(ticks):
        problems.append("tick records out of order")
    for ln, rec in records:
        if rec.get("kind") != "stats":
            continue
        cols = {k: v for k, v in rec.items()
                if isinstance(v, list)}
        lens = {len(v) for v in cols.values()}
        if len(lens) > 1:
            problems.append(f"line {ln}: stats columns have unequal "
                            f"lengths {sorted(lens)}")
    summaries = [rec for _, rec in records if rec.get("kind") == "summary"]
    if len(summaries) > 1:
        problems.append(f"{len(summaries)} summary records (want <= 1)")
    if summaries and rounds:
        if summaries[0].get("rounds") != len(rounds):
            problems.append(
                f"summary.rounds={summaries[0].get('rounds')} but stream "
                f"has {len(rounds)} round records")
    return problems


def _fmt_bytes(b):
    if b is None:
        return "-"
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if b >= div:
            return f"{b / div:.2f}{unit}"
    return f"{b}B"


def render(path, records):
    by_kind = {}
    for _, rec in records:
        by_kind.setdefault(rec.get("kind"), []).append(rec)
    man = by_kind.get("manifest", [{}])[0]
    rounds = by_kind.get("round", [])
    stats = by_kind.get("stats", [])
    summary = by_kind.get("summary", [{}])[-1]
    bench = by_kind.get("bench_row", [])

    print(f"run {man.get('run_id', '?')}  ({path})")
    print(f"  created      {man.get('created', '?')}  "
          f"git {str(man.get('git_sha'))[:12]}")
    print(f"  jax {man.get('jax_version', '?')}  "
          f"{man.get('platform', '?')} x{man.get('device_count', '?')}  "
          f"seed={man.get('seed')}")
    if man.get("argv"):
        print(f"  argv         {' '.join(man['argv'])}")

    if rounds:
        dts = [r["round_seconds"] for r in rounds
               if r.get("round_seconds") is not None]
        # the first recorded round carries the compile — steady state
        # excludes it (same convention as RunResult.compile_seconds)
        steady = dts[1:] or dts
        print(f"\nrounds: {len(rounds)}"
              + (f"  (first/compile {dts[0]*1e3:.1f}ms)" if dts else ""))
        if steady:
            mean = sum(steady) / len(steady)
            print(f"  steady-state {mean*1e3:.2f}ms/round  "
                  f"= {1.0/mean:.2f} rounds/sec")
        last = rounds[-1]
        if last.get("bytes_up") is not None:
            print(f"  wire totals  up={_fmt_bytes(last['bytes_up'])}  "
                  f"down={_fmt_bytes(last['bytes_down'])}")
        if last.get("samples") is not None:
            print(f"  cost         samples={last['samples']}  "
                  f"comms={last.get('comms')}")

    phases = summary.get("phases") or {}
    if phases:
        # shares are of self time (a span less its child spans), so nested
        # phases count each second once; older streams carry no self time
        own = {k: p.get("self_seconds", p["seconds"])
               for k, p in phases.items()}
        print("\nphase breakdown (total, self, count, share of self):")
        total = sum(own.values()) or 1.0
        for name, p in sorted(phases.items(), key=lambda kv: -own[kv[0]]):
            print(f"  {name:<16} {p['seconds']*1e3:9.1f}ms "
                  f"{own[name]*1e3:9.1f}ms  "
                  f"x{p['count']:<5d} {100 * own[name] / total:5.1f}%")

    if stats:
        cols = {}
        for s in stats:
            for k, v in s.items():
                if isinstance(v, list):
                    cols.setdefault(k, []).extend(v)
        print(f"\non-device stats ({len(stats)} drain(s), "
              f"{len(next(iter(cols.values()), []))} rounds):")
        for k, vs in cols.items():
            if vs:
                print(f"  {k:<16} last={vs[-1]:.4g}  "
                      f"mean={sum(vs)/len(vs):.4g}  max={max(vs):.4g}")

    reqs = by_kind.get("request", [])
    ticks = by_kind.get("tick", [])
    if reqs:
        lats = sorted(r["latency_s"] for r in reqs
                      if r.get("latency_s") is not None)
        toks = sum(r.get("new_tokens", 0) for r in reqs)
        reasons = {}
        for r in reqs:
            reasons[r["finish_reason"]] = reasons.get(r["finish_reason"], 0) + 1
        print(f"\nserve: {len(reqs)} requests, {toks} generated tokens, "
              f"{len(ticks)} ticks")
        if lats:
            p = lambda q: lats[min(int(q * len(lats)), len(lats) - 1)]
            print(f"  latency      p50={p(0.5)*1e3:.1f}ms  "
                  f"p99={p(0.99)*1e3:.1f}ms  max={lats[-1]*1e3:.1f}ms")
        print("  finish       "
              + "  ".join(f"{k}:{v}" for k, v in sorted(reasons.items())))
        if summary.get("requests_per_s") is not None:
            print(f"  throughput   {summary['requests_per_s']:.2f} req/s  "
                  f"{summary.get('tokens_per_s', 0):.1f} tok/s  "
                  f"(wall {summary.get('wall_s', 0):.2f}s)")
        if ticks:
            occ = [t.get("active", 0) for t in ticks]
            print(f"  occupancy    mean {sum(occ)/len(occ):.2f} / "
                  f"{max(occ)} max active slots")

    hist = summary.get("staleness_hist")
    if hist:
        print("\naccepted-staleness histogram (rounds): "
              + (" ".join(f"{s}:{int(k)}" for s, k in enumerate(hist) if k)
                 or "-"))

    if bench:
        print(f"\nbench rows ({len(bench)}):")
        for b in bench:
            print(f"  {b['name']:<28} {b['us_per_call']:12.1f} us/call"
                  + (f"  {b['derived']}" if b.get("derived") else ""))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", help="metrics JSONL stream (--metrics-out)")
    ap.add_argument("--check", action="store_true",
                    help="validate the stream instead of rendering it; "
                         "nonzero exit on any schema problem")
    args = ap.parse_args(argv)
    records = load(args.jsonl)
    problems = check(args.jsonl, records)
    if args.check:
        if problems:
            for p in problems:
                print(f"report: {p}", file=sys.stderr)
            return 1
        kinds = {}
        for _, rec in records:
            kinds[rec.get("kind")] = kinds.get(rec.get("kind"), 0) + 1
        print(f"report: OK — {len(records)} records "
              + " ".join(f"{k}:{v}" for k, v in sorted(kinds.items())))
        return 0
    if problems:
        for p in problems:
            print(f"report: WARNING: {p}", file=sys.stderr)
    render(args.jsonl, records)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
