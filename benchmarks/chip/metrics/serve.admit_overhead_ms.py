"""Median time of one admission beyond its prefill program: each
``bench.serve.admit`` span that lies wholly inside the traced window, less
the device time of the ``prefill_fn`` program inside it. What is left is
the prompt's transfer, the int8 quantise of the cache row, its scatter into
the pool, the first token's host sync and the bookkeeping. The row is
max_len wide, so this does not depend on the prompt's length.

A program is inside the span that holds its middle: the host and device
clocks of a v5e trace disagree by up to about a millisecond, enough to
place a prefill's start before the host dispatched it."""
import statistics

import tracing

SPAN = tracing.HOST_PREFIX + "serve.admit"
PROGRAM = "prefill_fn"


def read(L):
    win = tracing.host_window(L.events)
    prefills = [(p["start_ns"], p["start_ns"] + p["dur_ns"])
                for p in tracing.module_events(L.events, PROGRAM)]
    if win is None or not prefills:
        return None
    left = []
    for e in L.events:
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if e["name"] != SPAN or a < win[0] or b > win[1]:
            continue
        left.append(b - a - tracing.union_ns(
            [p for p in prefills if a <= (p[0] + p[1]) // 2 < b]))
    if not left:
        return None
    return statistics.median(left) / 1e6
