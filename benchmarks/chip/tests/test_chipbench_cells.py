"""Every cell of BENCHMARK.json resolves by name to its files, the file keeps
to the benchmark's contract, and run.py refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from chipbench import spec as S  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files():
    bench = _bench()
    for w in bench["workloads"]:
        cell = S.resolve(w["name"], bench)
        assert cell.config["name"] == w["config"]
        numbers = {"loss_gap", "loss_gap_mean"} & set(cell.limits)
        assert numbers and cell.limits["max_rise"] > 0
        assert all(cell.limits[n]["limit"] > 0 for n in numbers)
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        S.reference_module(cell.config["reference"])
        for m in cell.per_layer:
            assert callable(S.metric_reader(m["name"]))
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        assert all(m["moves"] in reported for m in cell.per_layer)


def test_benchmark_file_keeps_to_the_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    used = {w["config"] for w in bench["workloads"]}
    assert set(names) == used and len(set(names)) == len(names)
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                               "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert set(m.get("workloads", cells)) <= cells
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_peak_table_is_keyed_by_device_kind():
    assert S.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    try:
        S.peaks("TPU v0")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "sc2-asha-scan", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A directory with nothing but BENCHMARK.json and the benchmark's own
    files has no system under test: the run fails and prints no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "sc2-asha-scan", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=dict(os.environ, PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
