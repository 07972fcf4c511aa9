"""Share of its roofline that the int8-KV decode attention kernel reaches:
the least time for the bytes it must read (the valid int8 K/V rows and
their scales of every active slot, q and the output;
``counts.quant_decode``) and its flops, summed over the calls of the traced
window, over the device time of the kernel's events."""
import tracing

KERNEL = "quant_decode"


def read(L):
    ev = tracing.kernel_events(L.events, KERNEL)
    t = sum(e["dur_ns"] for e in ev) / 1e9
    if not t or not L.counts.get("qd_bytes"):
        return None
    p = L.peaks
    least = max(L.counts["qd_bytes"] / p["hbm_bytes_per_s"],
                L.counts["qd_flops"] / p["bf16_flops"])
    return 100.0 * least / t
