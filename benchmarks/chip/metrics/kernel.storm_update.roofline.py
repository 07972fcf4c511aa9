"""Share of its roofline that the STORM refresh kernel reaches: the least
time its bytes and flops allow (``counts.storm_update`` over the cohort's
flat v and w buffers, every local step of the traced rounds) over the
device time of the kernel's events."""
import tracing

KERNEL = "_storm_kernel"


def read(L):
    ev = tracing.kernel_events(L.events, KERNEL)
    t = sum(e["dur_ns"] for e in ev) / 1e9
    if not t:
        return None
    c, p = L.counts, L.peaks
    least = max(c["storm_bytes"] / p["hbm_bytes_per_s"],
                c["storm_flops"] / p["bf16_flops"]) * c["rounds"]
    return 100.0 * least / t
