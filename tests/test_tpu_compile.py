"""Main-path Pallas kernels compile for a described TPU v5e chip.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
one chip of a ``v5e:2x2`` topology described on this host, at the shapes
``chip_smoke.py`` drives. The compiler refuses what interpret mode accepts:
blocks not aligned to the (8, 128) tiling, more VMEM than a kernel may use.
The topology is described inside a fixture (only the worker that runs this
file loads the TPU library), and the persistent compile cache is off around
the compiles, since entries compiled for an absent chip cannot be read back.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.fed.serve import build_serve_fns
from repro.kernels.quant_decode import quant_decode_attention
from repro.kernels.quantize import dequantize, quantize_stoch
from repro.kernels.storm_update import adafbio_update, storm_update

X_LEN = 41_157_888       # whisper-tiny's flat upper-level x (tree_pack)
ODD_LEN = X_LEN + 1_037  # neither a block nor a lane multiple
COHORT = 8               # clients vmapped through one round program


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


ELEMENTWISE = {
    "storm": (lambda g, o, e, b: storm_update(g, o, e, b[0]), 3),
    "eq14": (lambda p, w, a, s: adafbio_update(p, w, a, s[0], s[1]), 3),
    "quantize": (lambda x, u, s: quantize_stoch(x, u, s[0], 127), 2),
}


@pytest.mark.parametrize("n", [X_LEN, ODD_LEN])
@pytest.mark.parametrize("name", sorted(ELEMENTWISE))
@pytest.mark.parametrize("clients", [0, COHORT])
def test_elementwise_kernel_compiles(one_chip, name, n, clients):
    """One flat buffer, or the cohort's buffers under a client vmap with
    per-client scalars, as the round program calls them."""
    fn, arrays = ELEMENTWISE[name]
    lead = (clients,) if clients else ()
    if clients:
        fn = jax.vmap(fn)
    shapes = [(lead + (n,), jnp.float32)] * arrays + [(lead + (2,),
                                                      jnp.float32)]
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("n", [X_LEN, ODD_LEN])
def test_dequantize_compiles(one_chip, n):
    _compile(lambda q, s: dequantize(q, s[0]), one_chip,
             ((n,), jnp.int8), ((1,), jnp.float32))


@pytest.mark.parametrize("b,h,kv,s,dh", [
    (8, 6, 6, 256, 64),          # chip_smoke serve: whisper-tiny, 8 slots
    (8, 40, 8, 32768, 128),      # long cache
    (64, 32, 8, 4096, 128),      # granite-4.0-h-small serving cell: G=4
])
def test_quant_decode_compiles(one_chip, b, h, kv, s, dh):
    _compile(lambda q, k8, ks, v8, vs, pos: quant_decode_attention(
                 q, k8, ks, v8, vs, pos, block_s=128),
             one_chip,
             ((b, h, dh), jnp.bfloat16), ((b, kv, s, dh), jnp.int8),
             ((b, kv, s), jnp.float32), ((b, kv, s, dh), jnp.int8),
             ((b, kv, s), jnp.float32), ((b,), jnp.int32))


# An instruction with an array result: name, dtype, dims, layout, opcode.
INSTR = re.compile(r"%\S+ = (\w+)\[([\d,]*)\](\{[^}]*\})? ([\w-]+)\(")


def _hbm_values(text):
    """(dtype, element count, opcode, line) of every instruction the
    program runs whose result is one array in HBM. Instructions inside a
    fusion are left out (they make no buffer of their own). A result laid
    out in another memory space (``S(n)`` in its layout) is the compiler
    staging a buffer in VMEM; so are async copies (``copy-start``/
    ``copy-done``), which move a staged buffer back. Where the whole pool
    fits in VMEM (the reduced size), the compiler stages it there in every
    layer."""
    fused = set(re.findall(r"\bfusion\(.*?calls=(%[\w.\-]+)", text))
    inside = False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            inside = head.group(1) in fused
        m = INSTR.search(line)
        if m and not inside and "S(" not in (m.group(3) or ""):
            dims = [int(d) for d in m.group(2).split(",") if d]
            yield m.group(1), math.prod(dims), m.group(4), line


def _moves_axes(line):
    perm = re.search(r"dimensions=\{([\d,]*)\}", line).group(1)
    perm = [int(d) for d in perm.split(",") if d]
    return perm != sorted(perm)


@pytest.mark.parametrize("n_layers,slots,max_len", [
    (2, 8, 512),                 # reduced
    (4, 32, 4096),               # the DeepSeek-67B serving cell's stage
])
def test_int8_decode_program_keeps_pool_in_place(one_chip, n_layers, slots,
                                                  max_len):
    """The serve engine's int8 decode program (Pallas kernel) for the
    DeepSeek-67B stage at published widths (KV 8, head_dim 128). The pool
    rides through the layer loop in the kernel's layout: no transpose
    moves an axis, no s8 value the size of one layer's pool slice is made
    in HBM, and each s8 pool leaf (k, v) is copied whole at most once (the
    decode does not donate its pool, so the loop's carry starts as a
    copy). The dots read each layer's MLP weights in place: no bf16 value
    the size of one layer's [d_model, d_ff] weight is made in HBM."""
    cfg = dataclasses.replace(get_arch("deepseek-67b"), n_layers=n_layers,
                              head_dim=128)
    fns = build_serve_fns(cfg, ShapeConfig("serve_decode", max_len, slots,
                                           "decode"),
                          None, kv_quant=True, kv_kernel="pallas")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)
    text = fns["decode"].lower(
        on_chip(fns["params_abs"]), on_chip(fns["cache_abs"]),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    pool = math.prod(fns["cache_abs"]["k"].shape)
    values = list(_hbm_values(text))
    assert [line for _, _, op, line in values
            if op == "transpose" and _moves_axes(line)] == []
    assert [op for dt, n, op, _ in values
            if dt == "s8" and n == pool // n_layers] == []
    assert len([op for dt, n, op, _ in values if dt == "s8" and n == pool
                and op == "copy"]) <= 2
    assert [op for dt, n, op, _ in values
            if dt == "bf16" and n == cfg.d_model * cfg.d_ff] == []


def test_hybrid_decode_program_keeps_state_and_experts_in_place(one_chip):
    """The serve engine's int8 decode program for granite-4.0-h-small at
    published widths (a Mamba, an attention and a Mamba layer; 9 of 72
    experts held; 8 slots): the pool rides through the layer loops, so
    the float32 Mamba state is copied whole at most once (the carry) and
    no value the size of one layer's state is made in HBM; each ragged
    expert matmul reads the stacked experts in place, so no value the size
    of one layer's held experts is made either."""
    cfg = get_arch("granite-4.0-h-small")
    cfg = dataclasses.replace(
        cfg, n_layers=3, layer_types=("mamba", "attention", "mamba"),
        moe=dataclasses.replace(cfg.moe, n_experts=9, n_routed=72))
    slots, max_len = 8, 512
    fns = build_serve_fns(cfg, ShapeConfig("serve_decode", max_len, slots,
                                           "decode"),
                          None, kv_quant=True, kv_kernel="pallas")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)
    text = fns["decode"].lower(
        on_chip(fns["params_abs"]), on_chip(fns["cache_abs"]),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    state = math.prod(fns["cache_abs"]["h"].shape)
    e = cfg.moe
    values = list(_hbm_values(text))
    assert len([op for dt, n, op, _ in values
                if dt == "f32" and n == state and op == "copy"]) <= 1
    assert [op for dt, n, op, _ in values
            if dt == "f32" and n == state // 2] == []
    assert [op for dt, n, op, _ in values if dt == "bf16" and n ==
            e.n_experts * cfg.d_model * e.d_ff_expert and op != "bitcast"] \
        == []
