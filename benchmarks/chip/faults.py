"""Faults planted in the timed path, for showing that the comparison
which decides ``correct`` catches them (on the CPU the tests in
``tests/bench_chip/``, on the chip ``tools/controls.py``). A training fault
takes the round program and the run and returns the program to use; a
serving fault takes the engine and the run and rewires the engine.
"""
from __future__ import annotations

from pathlib import Path

import harness

HERE = Path(__file__).resolve().parent


# ---------------------------------------------------------------- training

def unchanged_state(fn, run):
    """A round that hands its state back untouched."""
    def same(bank, last, ef, server, *rest):
        return bank, last, ef, server
    return same


def half_cohort(fn, run):
    """A round whose aggregate averages the first half of the cohort only
    (the other half's weights set to zero, the rest renormalised)."""
    import jax.numpy as jnp
    from repro.fed import population
    from repro.launch.train import population_round_program
    orig = population.staleness_weights

    def half(last_sync, ids, round_id, decay):
        w = orig(last_sync, ids, round_id, decay)
        w = jnp.where(jnp.arange(w.shape[0]) < w.shape[0] // 2, w, 0.0)
        return w / w.sum()
    # planted for the rest of the process: the program traces on its
    # first call
    population.staleness_weights = half
    drv = harness.load_module(HERE / "drivers" / "train_population.py")
    _, _, tr, specs, axes = drv.program(run.conf, run.traffic)
    return population_round_program(tr, run.n, specs, axes)


# ---------------------------------------------------------------- serving

def frozen_cache(engine, run):
    """A decode tick that leaves the KV pool as it found it."""
    decode = engine._decode

    def keep(params, pool, token, pos):
        logits, _ = decode(params, pool, token, pos)
        return logits, pool
    engine._decode = keep


def altered_token(engine, run):
    """Every fifth decode tick, each slot's token moved to the next id."""
    argmax = engine._argmax
    ticks = [0]

    def bump(lg):
        out = argmax(lg)
        if lg.shape[0] > 1:                    # decode ticks only
            ticks[0] += 1
            if ticks[0] % 5 == 0:
                out = (out + 1) % lg.shape[-1]
        return out
    engine._argmax = bump


TRAIN = {"unchanged_state": unchanged_state, "half_cohort": half_cohort}
SERVE = {"frozen_cache": frozen_cache, "altered_token": altered_token}
