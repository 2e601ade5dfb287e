"""The check that decides ``correct``.

Each trial's score is an answer that can be checked on its own: minus the
training loss at the trial's last applied step.  Once the window has
closed, the window's finished trials are replayed by the configuration's
plain reference (``references/<name>.py``) from the seed alone: the same
weights, tokens, hyperparameters and number of steps.

The trials are taken longest first, ties in an order drawn from the seed,
whatever their learning rate, until ``check_trials`` of them count or
``check_max_steps`` steps have been replayed (and past that cap only until
the first one counts).  A trial counts where the reference's loss never
rose more than the cell's ``max_rise`` nats above its first step's loss.
A trial whose loss climbs has left the stable regime of training: there
every rounding is amplified, and two sound computations (the program's
scan, per-step and serial drivers, and the reference at two matmul
precisions) part by up to a hundred times what they do on the way up
(``PERF.md`` gives the readings).  The rule reads the reference alone,
never the program or the learning rate.

The numbers, over the trials that count, in nats: ``loss_gap``, the widest
gap between a trial's served loss and the reference's, and
``loss_gap_mean``, their mean.  A cell compares those its limits file names.
"""
from __future__ import annotations

import random
import sys
import time
from typing import Any, Dict, List

from . import spec as S


def order(rows: List[Dict[str, Any]], seed: int) -> List[Dict[str, Any]]:
    """The window's finished trials, longest first, ties in seed order."""
    ok = [r for r in rows if r["ok"]]
    random.Random(seed).shuffle(ok)
    ok.sort(key=lambda r: -r["steps"])
    return ok


def check(cell: S.Cell, seed: int, rows: List[Dict[str, Any]],
          log=sys.stderr) -> Dict[str, Dict[str, float]]:
    tr, cfg, lim = cell.traffic, cell.config, cell.limits
    ref = S.reference_module(cfg["reference"])
    t = time.time()
    todo = order(rows, seed)
    losses = ref.replay(cfg, seed, tr["batch"], tr["seq"],
                        ({"config": r["config"], "stream": r["config"]["job_id"],
                          "budget": r["budget"], "steps": r["steps"]}
                         for r in todo))
    gaps, steps = [], 0
    for r, ls in zip(todo, losses):
        steps += r["steps"]
        rise = max(ls) - ls[0]
        counts = rise <= lim["max_rise"]
        gap = abs(-r["score"] - ls[-1])
        if counts:
            gaps.append(gap)
        print(f"trial {r['job_id']}: steps {r['steps']}/{r['budget']} "
              f"lr {r['config']['learning_rate']:.3g} served {-r['score']!r} "
              f"reference {ls[-1]!r} first {ls[0]!r} gap {gap!r} "
              f"rise {rise:.4f}{'' if counts else ' (climbed: not counted)'}",
              file=log)
        if len(gaps) >= tr["check_trials"] or (gaps and steps >= tr["check_max_steps"]):
            break
    print(f"reference: {len(gaps)} trials counted, {steps} steps replayed, "
          f"{time.time() - t:.1f} s", file=log)
    inf = float("inf")
    value = {"loss_gap": max(gaps, default=inf),
             "loss_gap_mean": sum(gaps) / len(gaps) if gaps else inf}
    return {k: {"value": value[k], "limit": lim[k]["limit"]}
            for k in value if k in lim}
