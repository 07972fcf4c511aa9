"""Serve-engine correctness: the continuous-batching scheduler changes
throughput, never results.

The load-bearing pins:
  * engine output == an independent B=1 sequential greedy loop over the
    same ``build_serve_fns`` programs (token-level: XLA's batched einsum
    reduction order differs from B=1 by ~1 ulp, argmax agrees at the
    fixed seeds);
  * engine output == the SAME engine serving one request at a time —
    the same compiled program plus bitwise row-independence of the
    batched decode makes this exact by construction;
  * the int8 KV-cache pool (Pallas kernel in interpret mode vs the XLA
    reference dequant) serves identical tokens;
  * hypothesis slot-lifecycle invariants: slots never double-book, every
    admitted request completes exactly once with a consistent finish
    reason.

MoE runs with drop-free capacity (finite capacity legitimately makes
token dropping depend on how many tokens share a dispatch).
"""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.configs.base import FedConfig, ShapeConfig
from repro.fed.serve import build_serve_fns
from repro.models import init_params, model_specs
from repro.serve import (Engine, LoadSpec, Request, generate_requests,
                         load_serve_params)
from repro.obs import NULL, MemorySink, Telemetry
from repro.serve.engine import QUANT_FAMILIES

FAMS = ["qwen1.5-4b",        # dense (MHA, qkv bias)
        "granite-20b",       # dense (MQA)
        "falcon-mamba-7b",   # ssm
        "zamba2-1.2b",       # hybrid
        "qwen3-moe-30b-a3b", # moe
        "whisper-tiny"]      # encdec
FAST = ["qwen1.5-4b", "falcon-mamba-7b"]
MAX_LEN = 24


def _cfg(arch_id):
    cfg = reduced(get_arch(arch_id), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    return cfg


def _params(cfg):
    return init_params(model_specs(cfg), jax.random.PRNGKey(0), "float32")


def _workload(cfg, n=5, seed=3, max_new=6, max_len=MAX_LEN):
    enc = (max_len, cfg.d_model) if cfg.family == "encdec" else None
    pre = ((cfg.n_prefix_embeds, cfg.d_model) if cfg.n_prefix_embeds
           else None)
    spec = LoadSpec(n_requests=n, prompt_lens=(4, 7), mean_new_tokens=4.0,
                    max_new_cap=max_new, seed=seed)
    return generate_requests(spec, cfg.vocab, enc_shape=enc,
                             prefix_shape=pre)


def _ref_sequential(cfg, params, reqs, max_len, eos_id=None):
    """Independent B=1 greedy loop straight over build_serve_fns — no
    engine, no slot pool, scalar pos. rid -> generated tokens."""
    pre = build_serve_fns(
        cfg, ShapeConfig("ref_pre", max_len, 1, "prefill"), None)
    dec = build_serve_fns(
        cfg, ShapeConfig("ref_dec", max_len, 1, "decode"), None)
    out = {}
    for req in reqs:
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             pre["cache_abs"])
        batch = {"tokens": jnp.asarray(np.asarray(req.tokens)[None])}
        if "prefix_embeds" in pre["batch_specs"]:
            spec = pre["batch_specs"]["prefix_embeds"]
            pe = req.prefix_embeds
            pe = np.zeros(spec.shape[1:], np.float32) if pe is None else pe
            batch["prefix_embeds"] = jnp.asarray(pe[None]).astype(spec.dtype)
        if "enc_embeds" in pre["batch_specs"]:
            batch["enc_embeds"] = jnp.asarray(req.enc_embeds[None]).astype(
                pre["batch_specs"]["enc_embeds"].dtype)
        logits, cache = pre["prefill"](params, batch, cache)
        toks = [int(jnp.argmax(logits[0, 0]))]
        pos, budget = int(np.shape(req.tokens)[-1]), req.max_new_tokens - 1
        while (budget > 0 and pos < max_len
               and not (eos_id is not None and toks[-1] == eos_id)):
            logits, cache = dec["decode"](
                params, cache, jnp.full((1, 1), toks[-1], jnp.int32),
                jnp.int32(pos))
            toks.append(int(jnp.argmax(logits[0, 0])))
            pos += 1
            budget -= 1
        out[req.rid] = toks
    return out


def _tokens(completions):
    return {c.rid: c.tokens for c in completions}


@pytest.mark.parametrize("arch_id", FAST)
def test_engine_matches_sequential(arch_id):
    """Continuous batching at slots=3 serves exactly what an independent
    one-request-at-a-time B=1 greedy loop produces."""
    cfg = _cfg(arch_id)
    params = _params(cfg)
    reqs = _workload(cfg)
    eng = Engine(cfg, params, slots=3, max_len=MAX_LEN)
    got = _tokens(eng.run(reqs))
    want = _ref_sequential(cfg, params, reqs, MAX_LEN)
    assert got == want


@pytest.mark.slow
@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("arch_id", FAMS)
def test_engine_one_at_a_time_matrix(arch_id, kv_quant):
    """Full family x quant matrix: the shared-pool engine vs the SAME
    engine class draining one request at a time. Identical programs plus
    bitwise decode row-independence make this exact."""
    cfg = _cfg(arch_id)
    if kv_quant and cfg.family not in QUANT_FAMILIES:
        pytest.skip(f"{cfg.family} keeps no attention KV cache")
    params = _params(cfg)
    reqs = _workload(cfg)
    shared = Engine(cfg, params, slots=4, max_len=MAX_LEN,
                    kv_quant=kv_quant)
    got = _tokens(shared.run(reqs))
    solo = Engine(cfg, params, slots=4, max_len=MAX_LEN, kv_quant=kv_quant)
    want = {}
    for r in reqs:
        want.update(_tokens(solo.run([r])))
    assert got == want


def test_engine_one_at_a_time_identity():
    """Fast-tier pin of the same-engine identity (dense arch)."""
    cfg = _cfg("qwen1.5-4b")
    params = _params(cfg)
    reqs = _workload(cfg, n=6)
    shared = Engine(cfg, params, slots=4, max_len=MAX_LEN)
    got = _tokens(shared.run(reqs))
    solo = Engine(cfg, params, slots=4, max_len=MAX_LEN)
    want = {}
    for r in reqs:
        want.update(_tokens(solo.run([r])))
    assert got == want


def test_kv_quant_kernel_matches_dequant():
    """int8 pool: the Pallas kernel (interpret mode — the TPU program on
    CPU) and the XLA reference dequant serve identical tokens."""
    cfg = _cfg("qwen1.5-4b")
    params = _params(cfg)
    reqs = _workload(cfg, n=4)
    ref = Engine(cfg, params, slots=3, max_len=MAX_LEN, kv_quant=True,
                 kv_kernel="xla")
    ker = Engine(cfg, params, slots=3, max_len=MAX_LEN, kv_quant=True,
                 kv_kernel="interpret")
    assert _tokens(ref.run(reqs)) == _tokens(ker.run(reqs))


def test_kv_quant_tracks_full_precision():
    """Greedy tokens through the int8 pool match the full-precision pool
    at the fixed seed — an empirical pin that the per-(token, head)
    scales hold quantization error below the argmax margin on this
    workload (the logit-level bound lives in tests/test_quant_decode.py)."""
    cfg = _cfg("qwen1.5-4b")
    params = _params(cfg)
    reqs = _workload(cfg, n=4)
    fp = Engine(cfg, params, slots=3, max_len=MAX_LEN)
    q8 = Engine(cfg, params, slots=3, max_len=MAX_LEN, kv_quant=True)
    assert _tokens(fp.run(reqs)) == _tokens(q8.run(reqs))


def test_eos_truncates_and_frees_slot():
    """With eos_id set to a token the no-eos run generated mid-sequence,
    that request retires at the first occurrence (eos included) and every
    other request's tokens are untouched."""
    cfg = _cfg("qwen1.5-4b")
    params = _params(cfg)
    reqs = _workload(cfg, n=5)
    base = _tokens(Engine(cfg, params, slots=2, max_len=MAX_LEN).run(reqs))
    rid, toks = next((r, t) for r, t in sorted(base.items())
                     if len(t) >= 3)
    eos = toks[1]
    done = Engine(cfg, params, slots=2, max_len=MAX_LEN,
                  eos_id=eos).run(reqs)
    got = _tokens(done)
    cut = base[rid].index(eos) + 1
    assert got[rid] == base[rid][:cut]
    assert next(c for c in done if c.rid == rid).finish_reason == "eos"
    for r, t in base.items():
        if r != rid and eos not in t:
            assert got[r] == t


def test_capacity_retirement():
    """A prompt near max_len truncates generation at the cache edge with
    finish_reason='capacity'."""
    cfg = _cfg("qwen1.5-4b")
    params = _params(cfg)
    max_len = 12
    req = Request(rid=0, tokens=np.arange(10, dtype=np.int32) % cfg.vocab,
                  max_new_tokens=30)
    done = Engine(cfg, params, slots=1, max_len=max_len).run([req])
    assert done[0].finish_reason == "capacity"
    # pos walks plen .. max_len; tokens = first (from prefill) + one per tick
    assert len(done[0].tokens) == max_len - 10 + 1


def test_submit_rejects_bad_requests():
    cfg = _cfg("qwen1.5-4b")
    params = _params(cfg)
    eng = Engine(cfg, params, slots=1, max_len=12)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(rid=0, tokens=np.zeros(0, np.int32)))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(rid=1, tokens=np.zeros(4, np.int32),
                           max_new_tokens=0))
    with pytest.raises(ValueError, match="prompt_len"):
        eng.submit(Request(rid=2, tokens=np.zeros(12, np.int32)))
    with pytest.raises(ValueError, match="slots"):
        Engine(cfg, params, slots=0, max_len=12)
    with pytest.raises(ValueError, match="kv_kernel"):
        Engine(cfg, params, slots=1, max_len=12, kv_kernel="cuda")


def test_kv_quant_rejects_stateful_families():
    cfg = _cfg("falcon-mamba-7b")
    with pytest.raises(ValueError, match="SSM state"):
        Engine(cfg, _params(cfg), slots=1, max_len=12, kv_quant=True)


# ---------------------------------------------------------------- spans

class _SpanLog:
    """Telemetry hook that logs each span's opening and closing and each
    tick record in call order, with the queue depth at every
    ``serve.prefill``."""

    def __init__(self, eng):
        self.eng, self.log = eng, []

    def __getattr__(self, name):
        return getattr(NULL, name)

    @contextlib.contextmanager
    def span(self, name):
        self.log.append(("open", name, self.eng.pending))
        yield
        self.log.append(("close", name, None))

    def tick(self, tick, **fields):
        self.log.append(("tick", tick, None))


def _span_tree(log):
    """[(name, children)] of the top-level spans; tick records become
    leaves named "tick"."""
    root, stack = [], []
    for kind, name, _ in log:
        if kind == "open":
            node = (name, [])
            (stack[-1][1] if stack else root).append(node)
            stack.append(node)
        elif kind == "close":
            assert stack.pop()[0] == name
        else:
            (stack[-1][1] if stack else root).append(("tick", []))
    assert not stack
    return root


def test_span_tree_per_tick():
    """Every tick is one ``serve.tick`` holding, in order, one
    ``serve.admit`` per admission (its ``serve.prefill``, then the first
    token's ``serve.sync``), then, when a slot is active, ``serve.decode``
    (holding the next tokens' ``serve.sync``) and ``serve.retire``; the tick
    record closes it. Admissions follow submission order."""
    cfg = _cfg("qwen1.5-4b")
    eng = Engine(cfg, _params(cfg), slots=2, max_len=MAX_LEN)
    reqs = _workload(cfg, n=5)
    for r in reqs:
        eng.submit(r)
    eng.tele = rec = _SpanLog(eng)
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
    tree = _span_tree(rec.log)
    assert [name for name, _ in tree] == ["serve.tick"] * steps
    admits = 0
    for _, kids in tree:
        names = " ".join(name for name, _ in kids)
        assert re.fullmatch(r"(serve\.admit )*(serve\.decode serve\.retire )?"
                            r"tick", names), names
        for name, sub in kids:
            inner = [n for n, _ in sub]
            want = {"serve.admit": ["serve.prefill", "serve.sync"],
                    "serve.decode": ["serve.sync"]}.get(name, [])
            assert inner == want and all(not s for _, s in sub), (name, sub)
            admits += name == "serve.admit"
    assert admits == len(reqs)
    assert [q for kind, name, q in rec.log
            if kind == "open" and name == "serve.prefill"] == \
        list(range(len(reqs) - 1, -1, -1))


def test_telemetry_keeps_tokens_and_host_syncs(monkeypatch):
    """A live Telemetry bus serves the same tokens as NULL through the
    same number of host syncs, and its phase totals count the span tree:
    one tick a step, one admit and one prefill a request, a sync in every
    admission and decode, one retire a decode."""
    counts = {"device_get": 0, "block_until_ready": 0}
    for fn in counts:
        real = getattr(jax, fn)

        def counted(*a, _fn=fn, _real=real, **k):
            counts[_fn] += 1
            return _real(*a, **k)
        monkeypatch.setattr(jax, fn, counted)
    cfg = _cfg("qwen1.5-4b")
    eng = Engine(cfg, _params(cfg), slots=2, max_len=MAX_LEN)
    reqs = _workload(cfg, n=5)
    live = Telemetry([MemorySink()])
    ran = []
    for tele in (NULL, live):
        eng.tele = tele
        for fn in counts:
            counts[fn] = 0
        ran.append((_tokens(eng.run(reqs)), dict(counts), eng._ticks))
    (want, syncs, t0), (got, syncs_on, t1) = ran
    assert got == want
    assert syncs_on == syncs and syncs["block_until_ready"] == 0
    ph = live.summary()["phases"]
    n = {k: v["count"] for k, v in ph.items()}
    assert n["serve.tick"] == t1 - t0
    assert n["serve.admit"] == n["serve.prefill"] == len(reqs)
    assert n["serve.sync"] == n["serve.admit"] + n["serve.decode"]
    assert n["serve.retire"] == n["serve.decode"]
    assert syncs_on["device_get"] == n["serve.sync"]
    tick = ph["serve.tick"]
    assert 0 < tick["self_seconds"] < tick["seconds"]


# -------------------------------------------------- lifecycle invariants

_MEMO = {}


def _hyp_model():
    if "m" not in _MEMO:
        cfg = _cfg("qwen1.5-4b")
        _MEMO["m"] = (cfg, _params(cfg))
    return _MEMO["m"]


def _check_lifecycle(slots, n, max_new, seed):
    """Scheduler invariants under a random workload: the slot ledger stays
    consistent every tick (free + occupied == slots, no rid in two slots),
    every submitted request completes exactly once, and each completion's
    token count and finish reason are mutually consistent."""
    cfg, params = _hyp_model()
    reqs = _workload(cfg, n=n, seed=seed, max_new=max_new, max_len=16)
    eng = Engine(cfg, params, slots=slots, max_len=16)
    for r in reqs:
        eng.submit(r)
    done = []
    while eng.has_work:
        done.extend(eng.step())
        occupied = [o.rid for o in eng._occupant if o is not None]
        assert len(eng._free) + len(occupied) == slots
        assert len(occupied) == len(set(occupied))
        assert eng.active <= slots
    got = {c.rid: c for c in done}
    assert sorted(got) == [r.rid for r in reqs]
    for r in reqs:
        c = got[r.rid]
        assert 1 <= len(c.tokens) <= r.max_new_tokens
        plen = int(np.shape(r.tokens)[-1])
        assert plen + len(c.tokens) - 1 <= 16
        if c.finish_reason == "length":
            assert len(c.tokens) == r.max_new_tokens
        elif c.finish_reason == "capacity":
            assert plen + len(c.tokens) - 1 == 16
        assert c.finished_s >= c.admitted_s >= 0.0


@pytest.mark.parametrize("slots,n,max_new,seed", [
    (1, 4, 3, 0),       # one-at-a-time: pure queueing
    (3, 7, 4, 1),       # more requests than slots: retire-and-refill
    (4, 2, 1, 2),       # budget 1: retirement at admission
])
def test_slot_lifecycle_invariants(slots, n, max_new, seed):
    _check_lifecycle(slots, n, max_new, seed)


try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings

    @settings(max_examples=6, deadline=None,
              suppress_health_check=list(hypothesis.HealthCheck))
    @given(slots=st.integers(1, 4), n=st.integers(1, 9),
           max_new=st.integers(1, 5), seed=st.integers(0, 2 ** 20))
    def test_slot_lifecycle_hypothesis(slots, n, max_new, seed):
        _check_lifecycle(slots, n, max_new, seed)
except ImportError:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_slot_lifecycle_hypothesis():
        pass


# ---------------------------------------------------------------- bridge

def _materialize(tree, key):
    leaves, td = jax.tree.flatten(tree)
    out = []
    for i, s in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if jnp.issubdtype(s.dtype, jnp.floating):
            out.append(jax.random.normal(k, s.shape).astype(s.dtype))
        else:
            out.append(jnp.zeros(s.shape, s.dtype))
    return jax.tree.unflatten(td, out)


def _fake_population_ckpt(path, cfg, n=3, step=7):
    """A launch/train.py population-layout checkpoint without training:
    (bank, last_sync, server) materialized from the trainer's own abstract
    templates."""
    from repro.checkpoint import save_checkpoint
    from repro.fed.runtime import FederatedTrainer
    tr = FederatedTrainer(cfg, FedConfig(), ShapeConfig("t", 8, 1, "train"),
                          mesh=None)
    key = jax.random.PRNGKey(5)
    bank = _materialize(tr.abstract_population_states(n), key)
    server = _materialize(tr.abstract_server_state(),
                          jax.random.fold_in(key, 99))
    state = (bank, jnp.zeros((n,), jnp.int32), server)
    save_checkpoint(str(path), state, step)
    return bank


def test_bridge_roundtrip(tmp_path):
    """load_serve_params recovers the client-mean global model from a
    population-layout checkpoint, bit-exact, with layout/step metadata."""
    cfg = _cfg("qwen1.5-4b")
    path = tmp_path / "ck"
    bank = _fake_population_ckpt(path, cfg, n=3, step=7)
    params, info = load_serve_params(str(path), cfg)
    assert info["clients"] == 3 and info["step"] == 7
    assert info["layout"].startswith("population")
    want_x = jax.tree.map(lambda a: jnp.mean(a, axis=0), bank["x"])
    for got, want in zip(jax.tree.leaves(params["x"]),
                         jax.tree.leaves(want_x)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the result is a servable params pytree
    eng = Engine(cfg, params, slots=1, max_len=12)
    assert eng.run(_workload(cfg, n=1, max_len=12))


def test_bridge_arch_mismatch_names_leaf(tmp_path):
    """A checkpoint trained at one size, served at another: the error
    names the offending leaf path (PR 4 convention), not a generic miss."""
    small = _cfg("qwen1.5-4b")
    path = tmp_path / "ck"
    _fake_population_ckpt(path, small)
    big = get_arch("qwen1.5-4b")     # full-size: same structure, new shapes
    with pytest.raises(ValueError, match=r"leaf \d+ at "):
        load_serve_params(str(path), big)


def test_bridge_missing_sidecar(tmp_path):
    cfg = _cfg("qwen1.5-4b")
    with pytest.raises(ValueError, match="sidecar"):
        load_serve_params(str(tmp_path / "nope"), cfg)
