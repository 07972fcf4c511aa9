"""Continuous-batching serve engine (docs/serving.md).

A fixed pool of B slots shares ONE jitted ``decode_step`` per tick with a
per-slot position vector. New requests prefill at batch 1, their cache row
scatters into the pool, and retired slots (EOS / token budget / cache
capacity) refill on the next tick — no head-of-line blocking on the longest
sequence. The scheduler changes throughput, never results: every cache leaf
carries the batch axis at position 1 and the decode path is bitwise
row-independent, so a request's tokens are identical whether it shared the
pool or ran alone (pinned in tests/test_serve_engine.py).

``kv_quant=True`` switches the pool to the int8 cache layout: prefill stays
full-precision (a direct int8 cast would be garbage), the row is quantized
per (token, head) and laid out heads-before-positions on the way into the
pool (``models.decode`` module docstring), and decode attends through
either the XLA reference dequant or the fused Pallas kernel
(``kv_kernel="pallas"``; ``"interpret"`` runs the same kernel on CPU).
A hybrid_moe pool holds the int8 K/V of its attention layers beside the
float32 mamba state of the others; the row's scatter writes both, so a
refilled slot starts from its own prompt's state. Its decode also counts
the tokens routed to each held expert, read at the tick's token sync.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.fed.serve import build_serve_fns
from repro.models.decode import quantize_cache
from repro.obs.telemetry import NULL

QUANT_FAMILIES = ("dense", "vlm", "moe", "encdec", "hybrid_moe")
KV_KERNELS = ("auto", "xla", "pallas", "interpret")


@dataclasses.dataclass
class Request:
    """One generation request. ``tokens``: [plen] int32 prompt.

    ``enc_embeds`` ([enc_len, d], encdec archs — enc_len must equal the
    engine's ``max_len``) and ``prefix_embeds`` ([n_prefix, d], VLM archs)
    ride along when the architecture needs them. ``arrival_s`` is the
    open-loop arrival offset stamped by the load generator."""
    rid: int
    tokens: np.ndarray
    max_new_tokens: int = 32
    arrival_s: float = 0.0
    enc_embeds: Optional[np.ndarray] = None
    prefix_embeds: Optional[np.ndarray] = None


@dataclasses.dataclass
class Completion:
    """A drained request: generated ``tokens`` (prompt excluded; EOS, when
    hit, included) plus scheduling timestamps in engine-clock seconds."""
    rid: int
    prompt_len: int
    tokens: List[int]
    finish_reason: str            # eos | length | capacity
    arrival_s: float
    admitted_s: float
    finished_s: float
    decode_ticks: int

    @property
    def latency_s(self) -> float:
        return self.finished_s - self.arrival_s


class Engine:
    """Continuous-batching greedy-decode engine over ``build_serve_fns``.

    ``submit()`` queues requests; ``step()`` runs one scheduler tick
    (admissions + one shared decode) and returns the requests that finished;
    ``run()`` drains the queue. Decoding is greedy argmax — the scheduler
    must be bit-reproducible, so sampling lives with the caller.
    """

    def __init__(self, cfg: ArchConfig, params, *, slots: int = 8,
                 max_len: int = 256, kv_quant: bool = False,
                 kv_kernel: str = "auto", mesh=None,
                 eos_id: Optional[int] = None, telemetry=NULL):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if kv_kernel not in KV_KERNELS:
            raise ValueError(f"kv_kernel must be one of {KV_KERNELS}, got "
                             f"{kv_kernel!r}")
        if kv_quant and cfg.family not in QUANT_FAMILIES:
            raise ValueError(
                f"kv_quant=True needs an attention KV cache; family "
                f"{cfg.family!r} keeps {'SSM state' if cfg.family == 'ssm' else 'hybrid state'} "
                f"(supported: {', '.join(QUANT_FAMILIES)})")
        if kv_kernel == "auto":
            kv_kernel = "pallas" if jax.default_backend() == "tpu" else "xla"
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.kv_quant = kv_quant
        self.kv_kernel = kv_kernel
        self.eos_id = eos_id
        self.tele = telemetry

        dec_shape = ShapeConfig("serve_decode", max_len, slots, "decode")
        pre_shape = ShapeConfig("serve_prefill", max_len, 1, "prefill")
        self._dec = build_serve_fns(cfg, dec_shape, mesh, kv_quant=kv_quant,
                                    kv_kernel=kv_kernel)
        self._pre = build_serve_fns(cfg, pre_shape, mesh)
        self._decode = self._dec["decode"]
        self._prefill = self._pre["prefill"]
        self._pool = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  self._dec["cache_abs"])
        if mesh is not None and "cache_shardings" in self._dec:
            self._pool = jax.device_put(self._pool,
                                        self._dec["cache_shardings"])
        self._zero_row = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                      self._pre["cache_abs"])
        self._argmax = jax.jit(
            lambda lg: jnp.argmax(lg[:, 0, :], axis=-1).astype(jnp.int32))
        self._scatter = jax.jit(self._scatter_row)
        self._quantize = jax.jit(quantize_cache) if kv_quant else None

        # host-side slot state
        self._queue: Deque[Request] = deque()
        self._occupant: List[Optional[Request]] = [None] * slots
        self._free: List[int] = list(range(slots))[::-1]   # pop() -> slot 0 first
        self._pos = np.zeros(slots, np.int32)
        self._last_tok = np.zeros(slots, np.int32)
        self._budget = np.zeros(slots, np.int32)
        self._out: Dict[int, List[int]] = {}
        self._admitted_s: Dict[int, float] = {}
        self._admit_tick: Dict[int, int] = {}
        self._ticks = 0
        self._t0 = time.perf_counter()

    # ------------------------------------------------------------ clock

    def start_clock(self) -> None:
        """Reset the engine clock (latencies are measured from here)."""
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    # ------------------------------------------------------------ pool ops

    @staticmethod
    def _scatter_row(pool, row, slot):
        """Write a prefilled B=1 cache row into pool slot ``slot`` — every
        leaf carries batch at axis 1, so one tree_map covers all families."""
        return jax.tree.map(
            lambda p, r: jax.lax.dynamic_update_slice_in_dim(
                p, r.astype(p.dtype), slot, axis=1), pool, row)

    # ------------------------------------------------------------ intake

    def submit(self, req: Request) -> None:
        plen = int(np.shape(req.tokens)[-1])
        if plen < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be "
                             f">= 1, got {req.max_new_tokens}")
        if plen >= self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt_len {plen} must be < the cache "
                f"capacity max_len={self.max_len} (the generation budget is "
                f"truncated at capacity, the prompt is not)")
        self._queue.append(req)

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def active(self) -> int:
        return self.slots - len(self._free)

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or self.active > 0

    # ------------------------------------------------------------ scheduler

    def _admit(self, req: Request, slot: int,
               completed: List[Completion]) -> None:
        batch = {"tokens": jnp.asarray(np.asarray(req.tokens, np.int32)[None])}
        if "prefix_embeds" in self._pre["batch_specs"]:
            pe = req.prefix_embeds
            if pe is None:
                pe = np.zeros((self.cfg.n_prefix_embeds, self.cfg.d_model),
                              np.float32)
            batch["prefix_embeds"] = jnp.asarray(pe[None]).astype(
                self._pre["batch_specs"]["prefix_embeds"].dtype)
        if "enc_embeds" in self._pre["batch_specs"]:
            if req.enc_embeds is None:
                raise ValueError(f"request {req.rid}: encoder-decoder arch "
                                 f"needs enc_embeds [{self.max_len}, d]")
            batch["enc_embeds"] = jnp.asarray(req.enc_embeds[None]).astype(
                self._pre["batch_specs"]["enc_embeds"].dtype)
        with self.tele.span("serve.prefill"):
            logits, row = self._prefill(self.params, batch, self._zero_row)
        if self._quantize is not None:
            row = self._quantize(row)
        self._pool = self._scatter(self._pool, row, jnp.int32(slot))
        ids = self._argmax(logits)
        with self.tele.span("serve.sync"):
            first = int(jax.device_get(ids)[0])
        plen = int(np.shape(req.tokens)[-1])
        now = self.now()
        self._occupant[slot] = req
        self._pos[slot] = plen
        self._last_tok[slot] = first
        self._budget[slot] = req.max_new_tokens - 1
        self._out[req.rid] = [first]
        self._admitted_s[req.rid] = now
        self._admit_tick[req.rid] = self._ticks
        if (self.eos_id is not None and first == self.eos_id):
            self._retire(slot, "eos", completed)
        elif req.max_new_tokens == 1:
            self._retire(slot, "length", completed)

    def _retire(self, slot: int, reason: str,
                completed: List[Completion]) -> None:
        req = self._occupant[slot]
        now = self.now()
        comp = Completion(
            rid=req.rid, prompt_len=int(np.shape(req.tokens)[-1]),
            tokens=self._out.pop(req.rid), finish_reason=reason,
            arrival_s=req.arrival_s,
            admitted_s=self._admitted_s.pop(req.rid), finished_s=now,
            decode_ticks=self._ticks - self._admit_tick.pop(req.rid))
        completed.append(comp)
        self.tele.request(
            rid=comp.rid, prompt_len=comp.prompt_len,
            new_tokens=len(comp.tokens), finish_reason=reason,
            latency_s=round(comp.latency_s, 6),
            queue_s=round(comp.admitted_s - comp.arrival_s, 6),
            decode_ticks=comp.decode_ticks)
        self._occupant[slot] = None
        self._free.append(slot)

    def step(self) -> List[Completion]:
        """One scheduler tick: admit into free slots, then ONE shared decode
        over every active slot. Returns the requests that completed.

        Spans (``docs/serving.md``): ``serve.tick`` holds the whole tick;
        in it each admission's ``serve.admit`` (holding ``serve.prefill``
        and the first token's ``serve.sync``), the ``serve.decode``
        dispatch (holding the next tokens' ``serve.sync``) and the
        ``serve.retire`` loop."""
        tele = self.tele
        completed: List[Completion] = []
        admitted, routed = 0, None
        with tele.span("serve.tick"):
            while self._queue and self._free:
                with tele.span("serve.admit"):
                    self._admit(self._queue.popleft(), self._free.pop(),
                                completed)
                admitted += 1
            active = [s for s in range(self.slots)
                      if self._occupant[s] is not None]
            if active:
                with tele.span("serve.decode"):
                    logits, pool = self._decode(
                        self.params, self._pool,
                        jnp.asarray(self._last_tok[:, None]),
                        jnp.asarray(np.maximum(self._pos, 1)))
                    routed = pool.pop("expert_tokens", None)
                    self._pool = pool
                    ids = self._argmax(logits)
                    with tele.span("serve.sync"):
                        nxt, routed = jax.device_get((ids, routed))
                        nxt = np.asarray(nxt)
                with tele.span("serve.retire"):
                    for s in active:
                        tok = int(nxt[s])
                        self._out[self._occupant[s].rid].append(tok)
                        self._pos[s] += 1
                        self._last_tok[s] = tok
                        self._budget[s] -= 1
                        if self.eos_id is not None and tok == self.eos_id:
                            self._retire(s, "eos", completed)
                        elif self._budget[s] <= 0:
                            self._retire(s, "length", completed)
                        elif self._pos[s] >= self.max_len:
                            self._retire(s, "capacity", completed)
            self._ticks += 1
            load = {}
            if routed is not None:
                load = {"expert_tokens_max": int(routed.max()),
                        "expert_tokens_mean": float(routed.mean())}
            tele.tick(self._ticks - 1, active=len(active),
                      admitted=admitted, completed=len(completed),
                      queue_depth=len(self._queue), **load)
        return completed

    def run(self, requests=None) -> List[Completion]:
        """Drain: submit ``requests`` (if given) and tick until idle."""
        for r in requests or ():
            self.submit(r)
        done: List[Completion] = []
        while self.has_work:
            done.extend(self.step())
        return done
