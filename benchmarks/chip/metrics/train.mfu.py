"""Whole round's share of the chip's bf16 peak: the flops the algorithm's
passes need per round (``counts.fed_round_flops``) times the rounds of the
traced window, over the window, over the peak."""


def read(L):
    if not L.counts["rounds"] or L.window_s <= 0:
        return None
    flops = L.counts["round_flops"] * L.counts["rounds"]
    return 100.0 * flops / L.window_s / L.peaks["bf16_flops"]
