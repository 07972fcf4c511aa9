"""Operations and bytes that the algorithm needs, computed from shapes.

These are the yardstick's numerators: a kernel's or a step's share of the
chip's peak is the least time these counts allow over the time measured.
They count the work the algorithm states, whatever implements it: no
recomputation (rematerialised forwards), no padding, no copies.
"""
from __future__ import annotations


def itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]


# ---------------------------------------------------------------- kernels

def storm_update(n: int, dtype: str):
    """est' = g_new + (1 - beta)(est - g_old) over n elements: reads three
    buffers, writes one; three flops an element."""
    b = itemsize(dtype)
    return {"flops": 3 * n, "bytes": 4 * b * n}


def quant_decode(positions, n_heads: int, n_kv: int, head_dim: int,
                 q_bytes: int = 2):
    """One call of int8-KV decode attention over rows holding
    ``positions`` cached tokens each: reads q, the valid int8 K and V rows
    and their f32 scales, writes the output."""
    toks = sum(int(p) for p in positions)
    rows = len(positions)
    kv_bytes = 2 * toks * n_kv * (head_dim * 1 + 4)
    q_io = 2 * rows * n_heads * head_dim * q_bytes
    flops = 4 * toks * n_heads * head_dim
    return {"flops": flops, "bytes": kv_bytes + q_io}


# ---------------------------------------------------------------- models

def _attn_params(c):
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return d * h * hd * 2 + 2 * d * kv * hd


def _mlp_params(c):
    return 3 * c["d_model"] * c["d_ff"]


def layer_flops(c, tokens: int, ctx: float, cross_frames: int = 0):
    """Forward flops of one decoder-style layer over ``tokens`` positions
    that attend to ``ctx`` keys on average; with ``cross_frames`` the layer
    also cross-attends to that many encoder frames (its K/V projections of
    the frames included)."""
    h, hd = c["n_heads"], c["head_dim"]
    f = 2 * tokens * (_attn_params(c) + _mlp_params(c))
    f += 4 * tokens * ctx * h * hd
    if cross_frames:
        d, kv = c["d_model"], c["n_kv_heads"]
        f += 2 * tokens * (2 * d * h * hd)            # cq, co
        f += 2 * cross_frames * (2 * d * kv * hd)     # ck, cv
        f += 4 * tokens * cross_frames * h * hd
    return f


def backbone_fwd(c, dec_tokens: int, seq: int, frames: int = 0,
                 enc_seq: int = 0):
    """Forward flops of the backbone over ``dec_tokens`` decoder positions
    in sequences of ``seq`` (causal) and, for an encoder-decoder, ``frames``
    encoder positions in sequences of ``enc_seq`` (bidirectional)."""
    n_seq = max(dec_tokens // max(seq, 1), 1)
    f = c["n_layers"] * layer_flops(
        c, dec_tokens, (seq + 1) / 2, frames // n_seq if frames else 0)
    if frames:
        f += c["encoder_layers"] * layer_flops(c, frames, enc_seq)
    return f


def head_fwd(c, tokens: int):
    return 2 * tokens * c["d_model"] * c["vocab"]


# ---------------------------------------------------------------- training

def fed_round_flops(c, fed, batch):
    """Flops one round of AdaFBiO needs: C clients x q local steps, each
    refreshing v (LL gradient in y at the new and the old point) and w (the
    Neumann hypergradient at both points). ``batch`` maps each of ``g``,
    ``f``, ``g0``, ``gi`` to (sequences, decoder length, encoder length).

    A forward over a batch is the backbone plus the head; the LL gradient
    in y is a forward plus the head's backward (2 head passes); the joint
    UL gradient is a forward and a backward (3 forwards); the mixed second
    derivative is counted as 4 forwards over zeta_0; the Neumann products
    need the K batches' features (one forward each, whatever depth is
    drawn) and, on average over the drawn depth, (K - 1) / 2 head-only
    Hessian products of 4 head passes each."""
    def fwd(key, with_head=True):
        n, s, se = batch[key]
        f = backbone_fwd(c, n * s, s, n * se, se)
        return f + (head_fwd(c, n * (s - 1)) if with_head else 0)

    def head(key):
        n, s, _ = batch[key]
        return head_fwd(c, n * (s - 1))

    K = fed["neumann_k"]
    v = fwd("g") + 2 * head("g")
    w = 3 * fwd("f") + fwd("gi", with_head=False) + \
        (K - 1) / 2 * 4 * head("gi") / K + 4 * fwd("g0")
    return fed["cohort"] * fed["q"] * 2 * (v + w)


# ---------------------------------------------------------------- serving

def prefill_flops(c, prompt_len: int):
    """Forward flops of one prompt: every layer over every position (causal
    attention) and the head at the last position only."""
    return backbone_fwd(c, prompt_len, prompt_len) + head_fwd(c, 1)


def decode_flops(c, positions):
    """Flops of one decode step for rows at ``positions`` cached tokens:
    each row's new token through every layer, attending to its cache, and
    the head."""
    f = 0
    for p in positions:
        f += c["n_layers"] * layer_flops(c, 1, p + 1) + head_fwd(c, 1)
    return f
