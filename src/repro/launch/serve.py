"""Serving launcher: continuous-batching greedy decode over a trained
checkpoint (docs/serving.md).

CPU usage (this container):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --reduced \
      --ckpt /tmp/ck --slots 8 --requests 32 --metrics-out serve.jsonl

Without --ckpt the engine serves a seed-initialized model (smoke runs).
On a real cluster the same entry point takes --mesh local for sharded
params/cache via ``build_serve_fns``.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_arch, reduced
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import init_params, model_specs
from repro.obs import make_telemetry
from repro.serve import Engine, LoadSpec, generate_requests, load_serve_params, replay
from repro.serve.engine import KV_KERNELS


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size variant of the same family")
    ap.add_argument("--ckpt", default=None,
                    help="launch/train.py checkpoint to serve (dense or "
                         "--ckpt-shards layout; repro.serve.bridge maps "
                         "the trained global state into serve params). "
                         "Omitted: seed-initialized params")
    ap.add_argument("--codec", default="none",
                    help="the TRAINING run's codec (none/int8/topk) — "
                         "needed to match lossy checkpoints' EF-bank "
                         "layout, lossless checkpoints ignore it")
    ap.add_argument("--slots", type=int, default=8,
                    help="continuous-batching slot-pool size (the shared "
                         "decode step's batch)")
    ap.add_argument("--max-len", type=int, default=256,
                    help="per-slot KV-cache capacity (prompt + generated)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV-cache pool: prefill rows quantize on "
                         "the way in, decode attends through the fused "
                         "dequant path (attention families only)")
    ap.add_argument("--kv-kernel", default="auto", choices=list(KV_KERNELS),
                    help="int8 decode attention path: pallas (TPU fused "
                         "kernel), xla (reference dequant), interpret "
                         "(the kernel in Pallas interpret mode, CPU-safe); "
                         "auto = pallas on TPU else xla")
    ap.add_argument("--mesh", default="none", choices=["none", "local"])
    ap.add_argument("--requests", type=int, default=32,
                    help="synthetic open-loop request count")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, requests/sec (0 = all "
                         "arrive at t=0: max-throughput drain)")
    ap.add_argument("--prompt-lens", default="8,16,32",
                    help="comma-separated prompt-length buckets (each "
                         "bucket compiles one prefill program)")
    ap.add_argument("--max-new", type=int, default=32,
                    help="per-request generation budget cap")
    ap.add_argument("--mean-new", type=float, default=16.0,
                    help="mean of the geometric output-length draw")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a slot when this token is generated "
                         "(default: budget/capacity retirement only)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed (params init when no --ckpt, and the "
                         "load generator's arrivals/prompts/budgets)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the serve telemetry stream (manifest + "
                         "request/tick records + span summary) to this "
                         "JSONL file; render/validate it with "
                         "scripts/report.py")
    ap.add_argument("--metrics-every", type=int, default=8,
                    help="flush buffered request/tick records every K "
                         "engine ticks")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="dump a TensorBoard-viewable jax.profiler trace "
                         "of the run to this directory: the engine's "
                         "serve.* spans on the device trace's clock")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.slots < 1:
        raise SystemExit("--slots must be >= 1")
    prompt_lens = tuple(int(x) for x in args.prompt_lens.split(","))
    if max(prompt_lens) >= args.max_len:
        raise SystemExit(f"--prompt-lens {max(prompt_lens)} must stay below "
                         f"--max-len {args.max_len} (the cache holds prompt "
                         f"+ generated tokens)")
    mesh = make_local_mesh() if args.mesh == "local" else None

    if args.ckpt:
        params, info = load_serve_params(args.ckpt, cfg, codec=args.codec)
        print(f"loaded {args.ckpt}: layout={info['layout']} "
              f"clients={info['clients']} step={info['step']}")
    else:
        params = init_params(model_specs(cfg), jax.random.PRNGKey(args.seed),
                             cfg.dtype)
        print("no --ckpt: serving seed-initialized params")

    tele = make_telemetry(args.metrics_out, args.metrics_every,
                          profile_dir=args.profile)
    tele.manifest(config=vars(args), seed=args.seed)
    try:
        engine = Engine(cfg, params, slots=args.slots, max_len=args.max_len,
                        kv_quant=args.kv_quant, kv_kernel=args.kv_kernel,
                        mesh=mesh, eos_id=args.eos_id, telemetry=tele)
        spec = LoadSpec(n_requests=args.requests, rate=args.rate,
                        prompt_lens=prompt_lens,
                        mean_new_tokens=args.mean_new,
                        max_new_cap=args.max_new, seed=args.seed)
        enc = ((args.max_len, cfg.d_model) if cfg.family == "encdec"
               else None)
        pre = ((cfg.n_prefix_embeds, cfg.d_model) if cfg.n_prefix_embeds
               else None)
        reqs = generate_requests(spec, cfg.vocab, enc_shape=enc,
                                 prefix_shape=pre)
        t0 = time.perf_counter()
        done = replay(engine, reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(c.tokens) for c in done)
        lats = sorted(c.latency_s for c in done)
        p = lambda q: lats[min(int(q * len(lats)), len(lats) - 1)]
        print(f"served {len(done)} requests in {wall:.2f}s — "
              f"{len(done) / wall:.2f} req/s, {toks / wall:.1f} tok/s, "
              f"p50 {p(0.5):.3f}s, p99 {p(0.99):.3f}s")
        tele.note(requests=len(done), wall_s=round(wall, 4),
                  requests_per_s=round(len(done) / wall, 4),
                  tokens_per_s=round(toks / wall, 3),
                  p50_s=round(p(0.5), 6), p99_s=round(p(0.99), 6))
    finally:
        tele.close()


if __name__ == "__main__":
    main()
