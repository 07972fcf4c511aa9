"""Record a small device trace for the harness's CPU tests.

    python3 benchmarks/chip/tools/record_trace.py OUT.json.gz

Runs, on the chip, a few calls of one jitted program that holds what the
reduction looks for: ops under the ``round/local_scan`` and ``round/codec``
scopes, the STORM refresh kernel and the int8-KV decode kernel, with a
benchmark host span around each call. The flattened events
(``tracing.capture``) and the program's HLO index (``tracing.hlo_index``)
are written gzipped; ``tests/bench_chip/test_bench_harness.py`` reduces them.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402
import tracing  # noqa: E402


def main():
    out = Path(sys.argv[1])
    harness.require_chips(1)
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.quant_decode import quant_decode_attention, quantize_kv

    @jax.jit
    def step(a, b, c, q, k8, ks, v8, vs):
        with jax.named_scope("round/local_scan"):
            x = ops.storm_update_tree({"p": a}, {"p": b}, {"p": c}, 0.3)["p"]
            y = jnp.tanh(x.reshape(1024, -1) @ x.reshape(-1, 1024))
        with jax.named_scope("round/codec"):
            z = jnp.sum(y * 2.0)
        o = quant_decode_attention(q, k8, ks, v8, vs,
                                   jnp.full((q.shape[0],), 300, jnp.int32),
                                   block_s=128)
        return z, o

    n = 1024 * 1024
    a = jnp.ones((n,), jnp.float32)
    kk = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 512, 128))
    k8, ks = quantize_kv(kk)
    args = (a, a * 0.5, a * 0.25, jnp.ones((4, 64, 128), jnp.bfloat16),
            k8, ks, k8, ks)
    jax.block_until_ready(step(*args))

    def calls():
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                jax.block_until_ready(step(*args))

    hlo = step.lower(*args).compile().as_text()
    _, events, window_s = tracing.capture(calls, HERE / ".." / ".." /
                                          ".bench_trace")
    module, index = tracing.hlo_index(hlo)
    tracing.save_events({"events": events, "indexes": {module: index}},
                        out)
    tracing.annotate(events, {module: index})
    print(f"{len(events)} events, window {window_s:.4f} s, busy "
          f"{tracing.busy_s(events):.6f} s -> {out}")
    names = sorted({e["name"] for e in tracing.device_ops(events)})
    print("device op names:", names[:60])
    print("scopes: local_scan", tracing.scope_s(events, "round/local_scan"),
          "codec", tracing.scope_s(events, "round/codec"), "kernels:",
          len(tracing.kernel_events(events, "_storm_kernel")),
          len(tracing.kernel_events(events, "quant_decode")))


if __name__ == "__main__":
    main()
