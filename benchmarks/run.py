"""Benchmark runner: one module per paper table/figure + the roofline report.

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --only fig3,roofline
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCHES = ("table1", "fig3", "fig4", "fig5", "extensibility", "roofline")
OUT_DIR = "artifacts/bench"


def _run_one(name: str):
    if name == "table1":
        from . import table1_switching as m
        return m.run()
    if name == "fig3":
        from . import fig3_scalability as m
        return m.run()
    if name == "fig4":
        from . import fig4_distributions as m
        return m.run()
    if name == "fig5":
        from . import fig5_hpo_curves as m
        return m.run()
    if name == "extensibility":
        from . import extensibility_loc as m
        return m.run()
    if name == "roofline":
        from . import roofline as m
        single = m.run("pod_16x16")
        multi = m.run("multipod_2x16x16")
        return {"single_pod": single, "multi_pod": multi,
                "pass": single["pass"] and multi["pass"]}
    raise KeyError(name)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default="", help="comma-separated subset of " + ",".join(BENCHES))
    args = p.parse_args(argv)
    names = [n for n in args.only.split(",") if n] or list(BENCHES)

    os.makedirs(OUT_DIR, exist_ok=True)
    all_ok = True
    for name in names:
        t0 = time.time()
        try:
            result = _run_one(name)
            status = "PASS" if result.get("pass", True) else "CHECK"
        except Exception as e:  # noqa: BLE001 - surface but keep running others
            import traceback
            result = {"error": traceback.format_exc()}
            status = "FAIL"
        dt = time.time() - t0
        all_ok &= status != "FAIL"
        with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
            json.dump(result, f, indent=1, default=str)
        claim = result.get("paper_claim", "")
        print(f"[{status}] {name:14s} {dt:7.1f}s  {claim}", flush=True)
        if name == "roofline" and "single_pod" in result:
            sp = result["single_pod"]
            print(f"         cells={sp['n_cells']} ok={sp['n_ok']} "
                  f"skipped={sp['n_skipped']} failed={sp['n_failed']} "
                  f"bottlenecks={sp['bottleneck_histogram']}")
    print(f"\nartifacts in {OUT_DIR}/")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
