"""The training cell at one local step a round
(``train.whisper-tiny.n8c8q1``) keeps the q=4 cell's limits: at a small
size on the CPU (``bench_cells.py``, with q set back to 1) a sound run is
correct, and the bfloat16 control and a round that returns its state
unchanged are not.

    python3 -m pytest -q tests/bench_chip/test_controls_train_q1.py
"""
from __future__ import annotations

import pytest

from bench_cells import run_cell, small_ctx
from faults import unchanged_state

Q1 = "train.whisper-tiny.n8c8q1"


def q1_ctx(**kw):
    ctx = small_ctx(Q1, **kw)
    ctx.traffic["q"] = 1
    return ctx


@pytest.mark.parametrize("kw, correct", [
    ({}, True),
    ({"dtype": "bfloat16"}, False),
    ({"fault": unchanged_state}, False),
], ids=["sound", "control_bfloat16", "unchanged_state"])
def test_q1_cell_limits(kw, correct):
    assert run_cell(q1_ctx(**kw)) is correct
