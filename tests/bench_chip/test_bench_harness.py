"""CPU tests of the chip benchmark's harness: the manifest, the traffic
generator, the counts of operations and bytes, the trace reduction on a
recorded trace, and the refusal to run without a TPU.

    python3 -m pytest -q tests/bench_chip
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import counts  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import tracing  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------- manifest

def test_manifest_names_and_units():
    m = MANIFEST
    names = [x["name"] for x in m["configs"] + m["workloads"] +
             m["end_to_end"] + m["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    assert all(NAME.match(w[k]) for w in m["workloads"]
               for k in ("config", "traffic"))
    assert all(NAME.match(k) for c in m["configs"] for k in c["reduced"])
    assert all(UNIT.match(x["unit"]) for x in m["end_to_end"] +
               m["per_layer"])
    assert len(set(names)) == len(names)


def test_every_cell_and_metric_has_its_files():
    m = MANIFEST
    by_name = {c["name"]: c for c in m["configs"]}
    for w in m["workloads"]:
        conf = json.loads((ROOT / by_name[w["config"]]["file"]).read_text())
        assert (CHIP / "drivers" / f"{conf['driver']}.py").exists()
        assert (CHIP / "traffic" / f"{w['traffic']}.json").exists()
        assert (CHIP / "limits" / f"{w['name']}.json").exists()
    for x in m["per_layer"]:
        assert (CHIP / "metrics" / f"{x['name']}.py").exists()
        assert x["moves"] in {e["name"] for e in m["end_to_end"]}


def test_every_cell_reports_setup_another_metric_and_a_layer():
    m = MANIFEST
    for w in m["workloads"]:
        e2e = [e["name"] for e in m["end_to_end"]
               if "workloads" not in e or w["name"] in e["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in x.get("workloads", []) for x in
                   m["per_layer"])


def test_config_files_state_their_reductions():
    for c in MANIFEST["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]


# ---------------------------------------------------------------- traffic

TRAFFIC = {"rate": 8.0, "prompt_lens": [128, 512, 1024, 2048],
           "prompt_weights": [0.4, 0.3, 0.2, 0.1], "mean_new_tokens": 128,
           "max_new_cap": 512, "shape_seed": 5}


def schedule(seed):
    reqs = loadgen.generate(TRAFFIC, 20.0, harness.np_rng(seed, 6), 1000)
    return reqs


def test_traffic_repeats_for_a_seed():
    a, b = schedule(2 ** 40 + 1), schedule(2 ** 40 + 1)
    assert [(r.arrival_s, r.max_new_tokens, r.tokens.tolist()) for r in a] \
        == [(r.arrival_s, r.max_new_tokens, r.tokens.tolist()) for r in b]


def test_seeds_share_the_work_in_another_order():
    a, b = schedule(3), schedule(4)
    assert sorted(len(r.tokens) for r in a) == \
        sorted(len(r.tokens) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert np.isclose(a[-1].arrival_s, b[-1].arrival_s)
    assert [len(r.tokens) for r in a] != [len(r.tokens) for r in b]
    assert all(r.max_new_tokens <= 512 for r in a)
    assert {len(r.tokens) for r in a} <= set(TRAFFIC["prompt_lens"])


def test_seed_words_cover_large_seeds():
    assert harness.seed_words(2 ** 33 + 5) == (5, 2)
    with pytest.raises(SystemExit):
        harness.seed_words(-1)


# ---------------------------------------------------------------- counts

def test_storm_update_counts_by_hand():
    # 1000 f32 elements: read g_new, g_old, est, write est' = 16 kB
    assert counts.storm_update(1000, "float32") == {"flops": 3000,
                                                    "bytes": 16000}
    assert counts.storm_update(10, "bfloat16")["bytes"] == 80


def test_quant_decode_counts_by_hand():
    # 2 rows with 3 and 5 cached tokens, 4 q heads over 2 kv heads, dim 8:
    # K and V int8 (8 B) plus one f32 scale each per token and kv head
    got = counts.quant_decode([3, 5], 4, 2, 8)
    kv = 2 * 8 * 2 * (8 + 4)
    q_io = 2 * 2 * 4 * 8 * 2
    assert got == {"bytes": kv + q_io, "flops": 4 * 8 * 4 * 8}


def test_layer_flops_by_hand():
    c = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
         "d_ff": 8, "vocab": 10}
    attn = 4 * 2 * 2 * 2 + 2 * 4 * 1 * 2        # q, o + k, v
    mlp = 3 * 4 * 8
    assert counts.layer_flops(c, 3, 2.0) == \
        2 * 3 * (attn + mlp) + 4 * 3 * 2.0 * 2 * 2
    assert counts.head_fwd(c, 5) == 2 * 5 * 4 * 10


# ---------------------------------------------------------------- trace

def ev(line, name, start, dur, tf_op="", plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": {"tf_op": tf_op} if tf_op else {}}


def test_union_of_intervals():
    assert tracing.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert tracing.union_ns([(0, 10), (2, 3)]) == 10
    assert tracing.union_ns([]) == 0


def test_nested_ops_count_once():
    e = [ev("XLA Ops", "while.1", 0, 100, "jit(f)/round/local_scan/while"),
         ev("XLA Ops", "fusion.3", 10, 30, "jit(f)/round/local_scan/mul"),
         ev("XLA Ops", "k", 50, 20, "jit(f)/round/local_scan/pallas_call"),
         ev("XLA Ops", "fusion.4", 120, 10, "jit(f)/round/codec/add")]
    assert tracing.scope_s(e, "round/local_scan") == pytest.approx(100e-9)
    assert tracing.busy_s(e) == pytest.approx(110e-9)
    top = dict(tracing.top_ops(e))
    assert top["jit(f)/round/local_scan/while"] == pytest.approx(50e-9)


def test_unnamed_ops_take_the_scope_around_or_inside_them():
    e = [ev("XLA Ops", "while.7", 0, 100),                  # no op_name
         ev("XLA Ops", "fusion.3", 10, 30, "jit(f)/round/codec/mul"),
         ev("XLA Ops", "fusion.4", 200, 100, "jit(f)/round/scatter/x"),
         ev("XLA Ops", "copy.1", 210, 10)]                   # no op_name
    assert tracing.scope_s(e, "round/codec") == pytest.approx(100e-9)
    assert tracing.scope_s(e, "round/scatter") == pytest.approx(100e-9)


def test_hlo_index_and_annotation():
    import base64
    body = base64.b64encode(b"\x00repro\x00kernels\x00storm_update\x00"
                            b"_storm_kernel\x00").decode()
    hlo = ("HloModule jit_round_fn, entry_computation_layout={}\n"
           "  %fusion.1 = f32[] fusion(%a), metadata={op_name="
           "\"jit(round_fn)/round/codec/add\" stack_frame_id=1}\n"
           "  %local_scan.1 = f32[8] custom-call(%b), custom_call_target="
           "\"tpu_custom_call\", metadata={op_name=\"jit(round_fn)/round/"
           "local_scan/pallas_call\"}, backend_config={\"custom_call_config"
           f"\":{{\"body\":\"{body}\"}}}}\n")
    module, index = tracing.hlo_index(hlo)
    assert module == "jit_round_fn"
    assert index["fusion.1"]["tf_op"] == "jit(round_fn)/round/codec/add"
    assert "_storm_kernel" in index["local_scan.1"]["kernel"]
    e = [ev("XLA Modules", "jit_round_fn(123)", 0, 100),
         ev("XLA Ops", "%fusion.1 = f32[] fusion(%a)", 10, 10),
         ev("XLA Ops", "%local_scan.1 = f32[8] custom-call(%b)", 30, 10),
         ev("XLA Modules", "jit_other(9)", 200, 50),
         ev("XLA Ops", "%fusion.1 = f32[] fusion(%c)", 210, 10)]
    tracing.annotate(e, {module: index})
    assert tracing.scope_s(e, "round/codec") == pytest.approx(10e-9)
    assert len(tracing.kernel_events(e, "_storm_kernel")) == 1


def test_reduction_on_hand_made_events():
    e = [ev("XLA Ops", "fusion.1", 0, 100, "jit(f)/round/local_scan/mul"),
         ev("XLA Ops", "fusion.2", 50, 100, "jit(f)/round/codec/add"),
         ev("XLA Ops", "k", 300, 50, "jit(f)/round/local_scan/_storm_kernel"),
         ev("XLA Modules", "jit_decode_fn", 0, 400),
         ev("bench", "bench.serve.prefill", 160, 100, plane="/host:CPU")]
    assert tracing.busy_s(e) == pytest.approx(200e-9)
    assert tracing.scope_s(e, "round/local_scan") == pytest.approx(150e-9)
    assert tracing.scope_s(e, "round/codec") == pytest.approx(100e-9)
    assert tracing.scope_s(e, "round/local") == 0
    assert len(tracing.kernel_events(e, "_storm_kernel")) == 1
    assert len(tracing.module_events(e, "decode_fn")) == 1
    assert tracing.idle_gaps(e) == [["bench.serve.prefill", 150e-9]]


RECORDED = CHIP / "testdata" / "v5e_round_trace.json.gz"


def test_reduction_on_recorded_trace():
    """A v5e trace of three calls of one program (``tools/record_trace.py``)
    with its compiled HLO's index: ops under two scopes, the STORM kernel
    and the int8 decode kernel."""
    rec = tracing.load_events(RECORDED)
    e = tracing.annotate(rec["events"], rec["indexes"])
    ops = tracing.device_ops(e)
    busy = tracing.busy_s(e)
    assert 0 < busy <= sum(x["dur_ns"] for x in ops) / 1e9
    assert len(tracing.module_events(e, "jit_step")) == 3
    # the codec scope's sum fuses into the local_scan matmul's fusion,
    # whose op_name is the matmul's: its device time is local_scan's
    assert 0 < tracing.scope_s(e, "round/local_scan") < busy
    assert tracing.scope_s(e, "round/codec") == 0
    assert len(tracing.kernel_events(e, "_storm_kernel")) == 3
    assert len(tracing.kernel_events(e, "quant_decode")) == 3


# ---------------------------------------------------------------- device

def test_run_refuses_a_machine_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = MANIFEST["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(CHIP / "run.py"), "--workload",
                        name, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], capture_output=True, text=True, env=env,
                       cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in
                   p.stdout.splitlines())
    assert "no accelerator" in p.stderr
