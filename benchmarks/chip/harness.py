"""What every cell shares: the manifest, the files a cell names, seeds, the
device check, the compile cache, the table of peaks, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own under ``configs/``, ``traffic/`` and
``metrics/``; ``run.py`` finds them by the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name):
    m = manifest()
    for w in m["workloads"]:
        if w["name"] == name:
            return m, w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def load_json(kind, name):
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_config(conf):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry it names, with the file's model numbers."""
    import dataclasses
    from repro.configs import get_arch
    from repro.configs.base import EncoderConfig
    m = conf["model"]
    return dataclasses.replace(
        get_arch(conf["arch"]), family=m["family"], n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab=m["vocab"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], dtype=m["dtype"],
        encoder=(EncoderConfig(n_layers=m["encoder_layers"])
                 if m.get("encoder_layers") else None))


def seed_words(seed: int):
    """Two 32-bit words from a seed of any size (the driver's are larger
    than a signed 32-bit int holds)."""
    if seed < 0:
        raise SystemExit("--seed must be >= 0")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def jax_key(seed: int, salt: int):
    import jax
    lo, hi = seed_words(seed)
    k = jax.random.PRNGKey(lo)
    return jax.random.fold_in(jax.random.fold_in(k, hi), salt)


def np_rng(seed: int, salt: int):
    import numpy as np
    return np.random.default_rng([salt, *seed_words(seed)])


def require_chips(n: int):
    """The device list, or NoDevice: the benchmark measures accelerators
    only and never falls back to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise NoDevice(f"no accelerator: JAX found platform "
                       f"{devs[0].platform!r}")
    if len(devs) < n:
        raise NoDevice(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def use_compile_cache():
    """JAX's persistent compile cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one. The same rule as the
    program's ``repro.launch.cache.use_compile_cache``, kept here with the
    rest of set-up so that a change to the program cannot move what
    ``setup_s`` measures; it also caches every program, however quickly
    it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peaks(device_kind: str):
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json")
    return table[device_kind]


def device_info(devs):
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def stage(name, t_start):
    """One line on stderr: how long the run has taken up to ``name``."""
    import time
    print(f"stage {name}: {time.perf_counter() - t_start:.2f} s",
          file=sys.stderr, flush=True)


def percentile(values, p):
    """Nearest-rank percentile of all values (no interpolation)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(math.ceil(p / 100 * len(v)) - 1, 0)]


class Checks:
    """The numbers that decide ``correct``, each beside its limit."""

    def __init__(self):
        self.items = []

    def add(self, name, value, limit, ok=None):
        value = float(value)
        if ok is None:
            ok = math.isfinite(value) and value <= limit
        self.items.append({"name": name, "value": value, "limit": limit,
                           "ok": bool(ok)})

    @property
    def correct(self):
        return bool(self.items) and all(c["ok"] for c in self.items)

    def lines(self):
        return [f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
                f"{'ok' if c['ok'] else 'FAIL'}" for c in self.items]


def emit(result: dict, checks: Checks):
    """Checks as the last lines of stderr; the result as the last line of
    stdout, with the checks under the last key."""
    result = dict(result)
    result["correct"] = checks.correct
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks.items}
    sys.stdout.flush()
    for line in checks.lines():
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
