#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print one result line.

    python3 benchmarks/chip/run.py --workload sc2-asha-scan --seed 7 \\
        --seconds 30 --trace 0

Run it from the root of a checkout.  It needs a TPU with as many chips as
the cell asks for: without one it exits 2 and prints no result.  The cell
(its configuration, traffic, limits and metrics) is read from
``BENCHMARK.json`` and the files under ``benchmarks/chip``.

Set-up (imports, device start, the trial's weights from the seed, every
program of the cell's job compiled or read from ``<checkout>/.jax_cache``, a
short warm-up job) is ``setup_s``.  Then a tuning job runs for ``--seconds``:
its proposer stops at the close and the job drains.  With ``--trace 0`` the
line carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics from a profiler trace of the job.  A sample of the job's trials is
then replayed by the plain reference; ``correct`` says whether each number
compared is inside its limit, and the numbers are printed beside their limits
as the last lines of standard error and under ``checks`` in the line.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", action="store_true",
                   help="run the configuration's lower-precision path (the "
                        "control of the check); not part of a measurement")
    args = p.parse_args(argv)

    # the compile cache lives at a fixed path inside the checkout, and keeps
    # every program of the cell (no eviction between the runs of a check)
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    from chipbench import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS,
                               control=args.control)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
