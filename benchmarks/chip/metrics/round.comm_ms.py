"""Device milliseconds per round of the round's exchange: the cohort
gather, the uplink codec, the aggregate with the server step, and the
write-back to the bank."""
import tracing

SCOPES = ("round/gather", "round/codec", "round/aggregate", "round/scatter")


def read(L):
    s = sum(tracing.scope_s(L.events, sc) for sc in SCOPES)
    if not s or not L.counts["rounds"]:
        return None
    return 1e3 * s / L.counts["rounds"]
