"""CPU tests of the hybrid serving cell (Mamba-2 and attention layers, an
MoE block of held experts in every layer) at a tiny size
(``hybrid_cells.py``): the served path against the float32 reference, the
comparison that decides ``correct`` against planted faults and its
control, the counts and the readers of the cell's per-layer metrics.

    python3 -m pytest -q tests/bench_chip/test_hybrid_cell.py
"""
from __future__ import annotations

import numpy as np
import pytest

from bench_cells import CHIP
from faults import altered_token, frozen_cache
from hybrid_cells import driver, small_conf, small_ctx

import counts_hybrid
import harness
import weights_hybrid as WH

# int8 K/V (one scale per token and head, rounding error up to 1/254 of
# the largest entry) and the bf16 conv state and prefilled K/V of the pool
# read 0.0043-0.0046 of the logits' spread here; a slot that keeps its
# previous Mamba state reads 0.014-0.040
LOGIT_TOL = 0.01


@pytest.mark.parametrize("kv_kernel", ["xla", "interpret"])
def test_engine_matches_reference_through_slot_refill(kv_kernel):
    """Prefill, then decode through ``serve.Engine`` with the int8 pool:
    every logit the engine computed, for three requests on two slots (the
    third refills the slot of the first), matches the reference's full
    forward logits at the same positions."""
    import jax
    from reference.granite_hybrid import logits_at
    from repro.serve.engine import Engine, Request
    conf = small_conf("float32")
    drv = driver()
    params = WH.make(conf, harness.jax_key(5, 1))
    eng = Engine(drv.arch_config(conf), params, slots=2, max_len=64,
                 kv_quant=True, kv_kernel=kv_kernel)
    seen = {"prefill": [], "decode": []}
    prefill, decode = eng._prefill, eng._decode

    def rec_prefill(p, batch, row):
        lg, row = prefill(p, batch, row)
        seen["prefill"].append(np.asarray(lg[0, 0]))
        return lg, row

    def rec_decode(p, pool, tok, pos):
        lg, pool = decode(p, pool, tok, pos)
        occ = [o.rid if o is not None else None for o in eng._occupant]
        seen["decode"].append((occ, np.asarray(lg[:, 0])))
        return lg, pool
    eng._prefill, eng._decode = rec_prefill, rec_decode
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(0, conf["vocab_size"], n,
                                               dtype=np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(12, 3), (20, 9), (9, 7)])]
    done = {c.rid: c.tokens for c in eng.run(reqs)}
    slot_of = {}
    for occ, _ in seen["decode"]:
        for s, rid in enumerate(occ):
            slot_of.setdefault(rid, s)
    assert slot_of[2] == slot_of[0]                  # a refilled slot
    for i, r in enumerate(reqs):
        got = [seen["prefill"][i]] + [lg[occ.index(r.rid)] for occ, lg
                                      in seen["decode"] if r.rid in occ]
        got = np.stack(got[:len(done[r.rid])])
        seq = np.concatenate([r.tokens, done[r.rid][:-1]])
        ref = np.asarray(logits_at(conf, params, seq,
                                   np.arange(len(r.tokens) - 1, len(seq))))
        gap = np.max(np.abs(got - ref) / ref.std(-1, keepdims=True))
        print(kv_kernel, r.rid, gap)
        assert gap < LOGIT_TOL


def test_hybrid_sound_run_is_correct():
    out = driver().run(small_ctx())
    print("\n".join(out["checks"].lines()))
    assert out["checks"].correct
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", [frozen_cache, altered_token])
def test_hybrid_fault_is_not_correct(fault):
    """A decode tick that keeps its pool (K/V and the Mamba state alike)
    unchanged, or an altered served token, fails the limit."""
    out = driver().run(small_ctx(fault=fault))
    print("\n".join(out["checks"].lines()))
    assert not out["checks"].correct


def test_hybrid_traced_counts_and_controls():
    """A traced slice counts flops, the int8 kernel's and the mixers'
    bytes of its ticks only; the float8 control's gap reads above the
    sound gap on the same requests."""
    import tracing
    ctx = small_ctx(trace=True)
    ctx.seconds = 4.0
    drv = driver()
    drv.SE.TRACE_SECONDS, drv.SE.TRACE_END_BEFORE = 1.5, 1.0
    r = drv.Run(ctx.conf, ctx.traffic, ctx.seed, ctx.limits)
    r.setup(ctx.seconds)
    done = []
    step = r.engine.step
    r.engine.step = lambda: done.extend(step()) or []
    r.window(ctx.seconds, ctx.traffic["drain_s"],
             tracing.Tracer(ctx.trace_dir))
    c = r.counts()
    assert all(c[k] > 0 for k in ("model_flops", "qd_bytes",
                                  "mamba_bytes", "mamba_flops"))
    r.traced = (r.traced[0], r.traced[0])
    assert r.counts()["mamba_bytes"] == 0
    out = drv.SE.outputs_of(done)
    sound, control = r.gap(out)[0], r.gap(out, lower=True)[0]
    print("sound", sound, "control", control)
    assert control > sound


def test_mamba_tick_counts_by_hand():
    c = small_conf()
    s = WH.sizes(c)                 # d 256, di 512, n 16, 16 heads of 32
    assert (s["Lm"], s["La"], s["conv_ch"]) == (2, 1, 544)
    got = counts_hybrid.mamba_tick(c, 3)
    weights = (256 * (2 * 512 + 2 * 16 + 16) + 512 * 256 + 4 * 544 + 544) \
        * 2 + (256 + 512 + 3 * 16) * 4
    state = 4 * 16 * 32 * 16 + 2 * 3 * 544
    assert got["bytes"] == 2 * (weights + 2 * 3 * state)
    assert got["flops"] == 3 * 2 * counts_hybrid.mamba_flops(c, 1)


def ev(line, name, start, dur, tf_op="", plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": {"tf_op": tf_op} if tf_op else {}}


def test_scope_readers_attribute_bare_ops_to_the_scope_before():
    """Ops that XLA named by their operation alone (a ragged matmul) take
    the scope of the op that ran before them; the median is over the
    decode program's runs."""
    from types import SimpleNamespace
    dec = "jit(decode_fn)/while/body/closed_call/decode"
    e = [ev("XLA Modules", "jit_decode_fn(1)", 0, 100),
         ev("XLA Ops", "a", 0, 10, dec + "/mamba/mul"),
         ev("XLA Ops", "b", 10, 20, dec + "/moe/dot_general"),
         ev("XLA Ops", "c", 30, 30, "ragged-dot-none"),
         ev("XLA Ops", "d", 60, 5, dec + "/mamba/add"),
         ev("XLA Modules", "jit_decode_fn(1)", 200, 100),
         ev("XLA Ops", "a", 200, 30, dec + "/mamba/mul"),
         ev("XLA Modules", "jit_prefill_fn(1)", 400, 100),
         ev("XLA Ops", "p", 400, 50, "jit(prefill_fn)/prefill/mamba/mul")]
    mamba = harness.load_module(CHIP / "metrics" / "serve.mamba_ms.py")
    moe = harness.load_module(CHIP / "metrics" / "serve.moe_ms.py")
    L = SimpleNamespace(events=e)
    assert mamba.per_run_ns(L, "decode/mamba") == [15, 30]
    assert mamba.read(L) == pytest.approx(22.5e-6)
    assert moe.read(L) == pytest.approx(25e-6)
