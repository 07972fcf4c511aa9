"""Median device time per decode tick of the Mamba-2 mixers: the self time
of the ops under the ``decode/mamba`` scope inside each run of the decode
program, the median over the runs of the traced slice.

Some instructions that XLA's passes make (a ragged matmul and its index
work, a gather) carry the bare name of their operation instead of the
name stack of the code they came from; such an op takes the scope of the
op that ran before it on its device."""
import bisect
import statistics

import tracing

PROGRAM = "decode_fn"
SCOPE = "decode/mamba"


def per_run_ns(L, scope):
    """Self time of the ops under ``scope`` in each run of the decode
    program ([] where it never ran)."""
    runs = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["plane"])
                  for e in tracing.module_events(L.events, PROGRAM))
    starts = [a for a, _, _ in runs]
    per = [0] * len(runs)
    last = {}
    for e in sorted(tracing.with_self_time(L.events),
                    key=lambda e: e["start_ns"]):
        name = tracing.scope_of(e)
        if "/" in name:
            last[e["plane"]] = name
        else:
            name = last.get(e["plane"], name)
        if f"/{scope}/" not in "/" + name + "/":
            continue
        mid = e["start_ns"] + e["dur_ns"] // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < runs[i][1] and e["plane"] == runs[i][2]:
            per[i] += e["self_ns"]
    return per


def read(L):
    per = per_run_ns(L, SCOPE)
    if not any(per):
        return None
    return statistics.median(per) / 1e6
