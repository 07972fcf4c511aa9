"""The comparison that decides ``correct`` has to fail a training run that
is wrong.

Each test drives a whole run of the training cell on the CPU at a small
size (``bench_cells.py``) and reads ``correct`` against the cell's
committed limits: a sound run comes out correct; the control, the
program's own bfloat16 path for the float32 cell, does not; nor does a
run with a fault planted in the timed path: a round that returns its
state unchanged, or averages half of the cohort only.

    python3 -m pytest -q tests/bench_chip/test_controls_train.py
"""
from __future__ import annotations

import pytest

from bench_cells import TRAIN, run_cell, small_ctx
from faults import half_cohort, unchanged_state


# ---------------------------------------------------------------- training

@pytest.fixture
def restore_weights():
    from repro.fed import population
    orig = population.staleness_weights
    yield
    population.staleness_weights = orig


def test_train_sound_run_is_correct():
    assert run_cell(small_ctx(TRAIN))


def test_train_control_bfloat16_is_not_correct():
    assert not run_cell(small_ctx(TRAIN, dtype="bfloat16"))


def test_train_unchanged_state_is_not_correct():
    assert not run_cell(small_ctx(TRAIN, fault=unchanged_state))


def test_train_half_cohort_is_not_correct(restore_weights):
    ctx = small_ctx(TRAIN, fault=half_cohort)
    ctx.traffic.update(population=4, cohort=4)
    assert not run_cell(ctx)
