"""A cell at smoke widths, for rehearsing a whole run on the CPU.

``tiny_cell(name)`` resolves a real cell and shrinks its widths, sequence
and warm-up so that a run, with its reference check, takes seconds on the
CPU.  The cell keeps its traffic kind, its proposer and its limits.  A
configuration with a ``smoke`` block shrinks by it, so that it keeps its
layer kinds; one without takes ``SMOKE`` and four heads (two k/v heads where
it groups them).
"""
from __future__ import annotations

import time
from typing import Any, Dict

from . import harness as H
from . import spec as S

SMOKE = {"d_model": 64, "d_ff": 128, "vocab_size": 256, "head_dim": 16}


def tiny_cell(name: str) -> S.Cell:
    cell = S.resolve(name)
    if "smoke" in cell.config:
        cell.config = dict(cell.config, **cell.config["smoke"])
    else:
        kv = 4 if cell.config["n_kv_heads"] == cell.config["n_heads"] else 2
        cell.config = dict(cell.config, n_heads=4, n_kv_heads=kv, **SMOKE)
    cell.traffic = dict(cell.traffic, seq=32, warmup_samples=3,
                        check_trials=4, check_max_steps=48)
    return cell


def run_tiny(name: str, seed: int = 2**31 + 11, seconds: float = 2.0,
             control: bool = False, trace: bool = False,
             log=None) -> Dict[str, Any]:
    import io

    from repro.train.population import clear_population_cache
    from repro.train.train_step import clear_step_cache

    clear_population_cache()
    clear_step_cache()
    try:
        return H.run_cell(name, seed, seconds, trace, time.time(),
                          require_chip=False, control=control,
                          cell=tiny_cell(name), log=log or io.StringIO())
    finally:
        clear_population_cache()
        clear_step_cache()
