"""Share of their roofline that the decode tick's Mamba-2 mixers reach:
the least time for the bytes they must move (every mixer's weights once,
each active slot's float32 state and conv state read and written;
``counts_hybrid.mamba_tick``) and their flops, summed over the ticks of the
traced slice, over the device time of the ops under ``decode/mamba`` in
the decode program's runs (``serve.mamba_ms``'s attribution)."""
from pathlib import Path

import harness

SCOPE = "decode/mamba"
MAMBA = harness.load_module(Path(__file__).with_name("serve.mamba_ms.py"))


def read(L):
    t = sum(MAMBA.per_run_ns(L, SCOPE)) / 1e9
    if not t or not L.counts.get("mamba_bytes"):
        return None
    p = L.peaks
    least = max(L.counts["mamba_bytes"] / p["hbm_bytes_per_s"],
                L.counts["mamba_flops"] / p["bf16_flops"])
    return 100.0 * least / t
