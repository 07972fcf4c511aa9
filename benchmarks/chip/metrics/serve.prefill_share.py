"""Share of the device's busy time that prefill programs took in the traced
window."""
import tracing

PROGRAM = "prefill_fn"


def read(L):
    busy = tracing.busy_s(L.events)
    ev = tracing.module_events(L.events, PROGRAM)
    if not busy or not ev:
        return None
    return 100.0 * sum(e["dur_ns"] for e in ev) / 1e9 / busy
