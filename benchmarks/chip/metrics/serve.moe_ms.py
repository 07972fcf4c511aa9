"""Median device time per decode tick of the MoE blocks (router, the held
experts' ragged matmuls, the shared expert): the self time of the ops
under the ``decode/moe`` scope inside each run of the decode program, as
``serve.mamba_ms`` reads its own scope."""
import statistics
from pathlib import Path

import harness

SCOPE = "decode/moe"
MAMBA = harness.load_module(Path(__file__).with_name("serve.mamba_ms.py"))


def read(L):
    per = MAMBA.per_run_ns(L, SCOPE)
    if not any(per):
        return None
    return statistics.median(per) / 1e6
