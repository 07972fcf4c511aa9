"""Median device time of one run of the decode-step program: the shared
tick that every active slot's next token comes from."""
import statistics

import tracing

PROGRAM = "decode_fn"


def read(L):
    ev = tracing.module_events(L.events, PROGRAM)
    if not ev:
        return None
    return 1e3 * statistics.median(e["dur_ns"] for e in ev) / 1e9
