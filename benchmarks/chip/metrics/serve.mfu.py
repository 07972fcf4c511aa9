"""Whole serving step's share of the chip's bf16 peak: the model flops of
every prompt prefilled and every token decoded in the traced window
(``counts.prefill_flops``, ``counts.decode_flops``), over the window, over
the peak."""


def read(L):
    f = L.counts.get("model_flops")
    if not f or L.window_s <= 0:
        return None
    return 100.0 * f / L.window_s / L.peaks["bf16_flops"]
