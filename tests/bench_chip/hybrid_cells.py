"""A small CPU version of the hybrid serving cell, for the tests in this
directory: the configuration's published keys cut to a tiny model (three
layers, Mamba, attention, Mamba; 4 of 8 experts held), the serving pool and
the traffic cut as ``bench_cells.small_ctx`` cuts the dense cells'."""
from __future__ import annotations

import time
from types import SimpleNamespace

from bench_cells import CHIP, ROOT, harness

HYBRID = "serve.granite-4.0-h-small-10l.overload"

TINY = {"hidden_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "attention_multiplier": 1 / 64,
        "mamba_n_heads": 16, "mamba_d_head": 32, "mamba_d_state": 16,
        "intermediate_size": 128, "shared_intermediate_size": 128,
        "num_local_experts": 4, "num_experts_per_tok": 2,
        "vocab_size": 4096, "num_hidden_layers": 3,
        "layer_types": ["mamba", "attention", "mamba"]}


def small_conf(dtype=None):
    _, w = harness.cell(HYBRID)
    conf = harness.load_json("configs", w["config"])
    conf.update(TINY)
    conf["published"] = dict(conf["published"], num_local_experts=8)
    conf["serve"].update(slots=4, max_len=160)
    if dtype:
        conf["dtype"] = dtype
    return conf


def small_ctx(dtype=None, fault=None, seed=2 ** 35 + 11, trace=False):
    import jax
    manifest, w = harness.cell(HYBRID)
    traffic = harness.load_json("traffic", w["traffic"])
    traffic.update(prompt_lens=[8, 16, 32, 64], rate=20.0,
                   mean_new_tokens=16, max_new_cap=64, drain_s=30)
    return SimpleNamespace(
        manifest=manifest, cell=w, conf=small_conf(dtype), traffic=traffic,
        limits=harness.load_json("limits", HYBRID), seed=seed, seconds=2.0,
        trace=trace, t_start=time.perf_counter(), devs=jax.devices(),
        fault=fault, trace_dir=ROOT / ".bench_trace")


def driver():
    return harness.load_module(CHIP / "drivers" / "serve_hybrid.py")
