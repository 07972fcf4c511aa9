"""Median time in one engine tick in which no op ran on the device: each
``bench.serve.tick`` span that lies wholly inside the traced window, less
the union of the device ops inside it. It is the time the device waits
for the engine's host work within a tick: input transfers and dispatch,
the return of the token sync, retirement.

An op is inside the span that holds its middle: the host and device clocks
of a v5e trace disagree by up to about a millisecond."""
import bisect
import statistics

import tracing

SPAN = tracing.HOST_PREFIX + "serve.tick"


def read(L):
    win = tracing.host_window(L.events)
    ops = sorted((e["start_ns"] + e["dur_ns"] // 2, e["start_ns"],
                  e["start_ns"] + e["dur_ns"])
                 for e in tracing.device_ops(L.events))
    if win is None or not ops:
        return None
    mids = [m for m, _, _ in ops]
    idle = []
    for e in L.events:
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if e["name"] != SPAN or a < win[0] or b > win[1]:
            continue
        inside = ops[bisect.bisect_left(mids, a):bisect.bisect_left(mids, b)]
        idle.append(b - a - tracing.union_ns([(s, t) for _, s, t in inside]))
    if not idle:
        return None
    return statistics.median(idle) / 1e6
