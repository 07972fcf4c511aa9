"""Operations and bytes that the hybrid configuration's serving needs,
computed from its shapes (the catalog keys of its configuration file).

As in ``counts.py``: the work the algorithm states, whatever implements
it. The Mamba-2 mixer counts its recurrence (five flops per element of the
state a token), not the chunked dual form that computes it in parallel.
The routed experts count the pairs that reach the held experts under even
routing: each token's ``num_experts_per_tok`` pairs land on a held expert
with the share held / routed.
"""
from __future__ import annotations

import weights_hybrid as WH


def _mamba_params(s):
    """The mixer's matmul weights (in_proj, out_proj)."""
    return s["d"] * (2 * s["di"] + 2 * s["n"] + s["nh"]) + s["di"] * s["d"]


def _attn_params(s):
    return 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def _moe_flops(s, tokens):
    routed = tokens * s["k"] * s["e"] / s["routed"]
    return (2 * tokens * s["d"] * s["routed"]
            + 2 * routed * 3 * s["d"] * s["f"]
            + 2 * tokens * 3 * s["d"] * s["fs"])


def _sizes(c):
    return dict(WH.sizes(c), k=c["num_experts_per_tok"])


def mamba_flops(c, tokens):
    """Flops of one mixer over ``tokens`` positions."""
    s = _sizes(c)
    return tokens * (2 * _mamba_params(s) + 2 * s["cw"] * s["conv_ch"]
                     + 5 * s["nh"] * s["p"] * s["n"])


def backbone_fwd(c, tokens, ctx):
    """Forward flops of every layer over ``tokens`` positions that attend
    to ``ctx`` keys on average."""
    s = _sizes(c)
    f = s["Lm"] * mamba_flops(c, tokens)
    f += s["La"] * (2 * tokens * _attn_params(s)
                    + 4 * tokens * ctx * s["h"] * s["hd"])
    return f + s["L"] * _moe_flops(s, tokens)


def head_fwd(c, tokens):
    return 2 * tokens * c["hidden_size"] * c["vocab_size"]


def prefill_flops(c, prompt_len):
    return backbone_fwd(c, prompt_len, (prompt_len + 1) / 2) + head_fwd(c, 1)


def decode_flops(c, positions):
    """One decode step for rows at ``positions`` cached tokens."""
    return sum(backbone_fwd(c, 1, p + 1) + head_fwd(c, 1) for p in positions)


def mamba_tick(c, rows):
    """The least the mixers of one decode tick must move and compute for
    ``rows`` active slots: every mixer's weights read once; each row's
    float32 state and conv state read and written."""
    s = _sizes(c)
    w = 2 if c["dtype"] == "bfloat16" else 4
    weights = (_mamba_params(s) + s["cw"] * s["conv_ch"] + s["conv_ch"]) * w \
        + (s["d"] + s["di"] + 3 * s["nh"]) * 4        # norms, A, dt, D
    state = 4 * s["nh"] * s["p"] * s["n"] + w * (s["cw"] - 1) * s["conv_ch"]
    return {"bytes": s["Lm"] * (weights + 2 * rows * state),
            "flops": rows * s["Lm"] * mamba_flops(c, 1)}
