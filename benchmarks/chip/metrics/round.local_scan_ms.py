"""Device milliseconds per round of the ops under ``round/local_scan``: the
q local steps (hypergradient passes and the update kernels)."""
import tracing


def read(L):
    s = tracing.scope_s(L.events, "round/local_scan")
    if not s or not L.counts["rounds"]:
        return None
    return 1e3 * s / L.counts["rounds"]
