"""Every cell of BENCHMARK.json resolves by name to its files, the file keeps
to the benchmark's contract, a configuration file states exactly the model
the trial builds, and run.py refuses to run without a TPU."""
import dataclasses
import json
import os
import re
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import pytest  # noqa: E402

from chipbench import modelfile as M  # noqa: E402
from chipbench import spec as S  # noqa: E402
from chipbench import testing as T  # noqa: E402

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


def _file(path):
    with open(path) as f:
        return json.load(f)


MLA_MOE = os.path.join(BENCH, "tests", "deepseek-v2-lite-smoke.json")
SC2 = os.path.join(BENCH, "configs", "starcoder2-3b-2L.json")
PHI3 = os.path.join(BENCH, "configs", "phi3-mini-2L.json")

# what the fixed list of 13 keys passed before the files were read by field
_SC2_OVER = {
    "d_model": 3072, "n_layers": 2, "n_heads": 24, "n_kv_heads": 2,
    "head_dim": 128, "d_ff": 12288, "vocab_size": 49152, "activation": "gelu",
    "tie_embeddings": True, "rope_theta": 999999.0, "norm_eps": 1e-06,
    "param_dtype": "float32", "compute_dtype": "float32",
    "name": "starcoder2-3b-2L"}
_PHI3_OVER = {
    "d_model": 3072, "n_layers": 2, "n_heads": 32, "n_kv_heads": 32,
    "head_dim": 96, "d_ff": 8192, "vocab_size": 32064, "activation": "swiglu",
    "tie_embeddings": False, "rope_theta": 10000.0, "norm_eps": 1e-06,
    "param_dtype": "float32", "compute_dtype": "float32",
    "name": "phi3-mini-2L"}
_BF16 = {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}


@pytest.mark.parametrize("path,control,want", [
    (SC2, False, _SC2_OVER), (SC2, True, dict(_SC2_OVER, **_BF16)),
    (PHI3, False, _PHI3_OVER), (PHI3, True, dict(_PHI3_OVER, **_BF16))])
def test_todays_files_pass_the_overrides_they_passed(path, control, want):
    over = M.model_overrides(_file(path), control)
    assert over == want
    assert [type(v) for v in over.values()] == [type(want[k]) for k in over]


def _resolve_with(tmp_path, config):
    """Resolve sc2-asha-scan with its configuration file replaced."""
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    bench = _bench()
    for c in bench["configs"]:
        c["file"] = str(path)
    return S.resolve("sc2-asha-scan", bench)


def test_a_key_that_runs_nothing_fails_to_resolve(tmp_path):
    with pytest.raises(M.ConfigError, match="expert_d_ff") as e:
        _resolve_with(tmp_path, dict(_file(SC2), expert_d_ff=1408))
    assert str(tmp_path / "config.json") in str(e.value)
    with pytest.raises(M.ConfigError, match="n_expert"):
        _resolve_with(tmp_path, dict(_file(SC2), control={"n_expert": 8}))
    assert _resolve_with(tmp_path, _file(SC2)).config == _file(SC2)


def test_a_pattern_the_trial_does_not_build_is_refused():
    cfg = _file(MLA_MOE)
    for key in ("pattern", "prefix_pattern"):
        with pytest.raises(M.ConfigError, match="the trial builds"):
            M.model_overrides({k: v for k, v in cfg.items() if k != key}, False)
    # a dense file on the MLA preset states the dense pattern it gets
    dense = dict(_file(SC2), preset="deepseek-v2-lite-16b")
    with pytest.raises(M.ConfigError, match="the trial builds"):
        M.model_overrides(dense, False)
    dense.update(pattern=[["attn", "dense"]], prefix_pattern=[])
    assert M.model_overrides(dense, False)["pattern"] == (("attn", "dense"),)


@pytest.mark.parametrize("path,field", [
    (MLA_MOE, "moe_d_ff"), (MLA_MOE, "capacity_factor"),
    (MLA_MOE, "kv_lora_rank"), (MLA_MOE, "rope_head_dim"), (SC2, "d_ff"),
    (SC2, "n_kv_heads")])
def test_a_file_that_leaves_out_a_field_its_layers_use_is_refused(path, field):
    cfg = {k: v for k, v in _file(path).items() if k != field}
    with pytest.raises(M.ConfigError, match=field):
        M.model_overrides(cfg, False)


@pytest.mark.parametrize("layer", [["mamba", "none"], ["attn_local", "dense"]])
def test_a_layer_kind_without_fields_is_refused(layer):
    cfg = dict(_file(SC2), pattern=[layer])
    with pytest.raises(M.ConfigError, match=layer[0]):
        M.model_overrides(cfg, False)


def test_the_mla_moe_file_is_the_model_the_trial_builds():
    from repro.configs import get_smoke_config

    cfg = _file(MLA_MOE)
    for control in (False, True):
        over = M.model_overrides(cfg, control)
        model = dataclasses.replace(get_smoke_config(cfg["preset"]), **over)
        stated = dict(cfg, **(cfg["control"] if control else {}))
        for key in over:
            want = stated[key]
            if key in ("pattern", "prefix_pattern"):
                want = tuple(tuple(layer) for layer in want)
            assert getattr(model, key) == want, key
        assert model.capacity_factor == 1.0 and model.moe_d_ff == 32


def test_copied_source_keys_must_agree_with_what_runs():
    cfg = dict(_file(MLA_MOE), hidden_size=64, n_routed_experts=8,
               moe_intermediate_size=32, model_type="deepseek_v2",
               source_keys=["hidden_size", "n_routed_experts",
                            "moe_intermediate_size", "model_type"])
    assert "hidden_size" not in M.model_overrides(cfg, False)
    with pytest.raises(M.ConfigError, match="moe_intermediate_size"):
        M.model_overrides(dict(cfg, moe_intermediate_size=1408), False)
    unlisted = dict(cfg, source_keys=["hidden_size"])
    with pytest.raises(M.ConfigError, match="model_type"):
        M.model_overrides(unlisted, False)


def test_tiny_cell_shrinks_todays_cells_as_before():
    for w in _bench()["workloads"]:
        real, tiny = S.resolve(w["name"]), T.tiny_cell(w["name"])
        kv = 4 if real.config["n_kv_heads"] == real.config["n_heads"] else 2
        assert tiny.config == dict(real.config, n_heads=4, n_kv_heads=kv,
                                   d_model=64, d_ff=128, vocab_size=256,
                                   head_dim=16)
        assert tiny.traffic == dict(real.traffic, seq=32, warmup_samples=3,
                                    check_trials=4, check_max_steps=48)


def test_tiny_cell_takes_a_files_own_smoke_block(monkeypatch):
    real = S.resolve("sc2-asha-scan")
    smoke = {"d_model": 32, "n_heads": 2, "n_kv_heads": 2, "head_dim": 16}
    real.config = dict(real.config, smoke=smoke)
    monkeypatch.setattr(T.S, "resolve", lambda name: real)
    tiny = T.tiny_cell("sc2-asha-scan")
    assert tiny.config == dict(real.config, **smoke)
    M.model_overrides(tiny.config, False)
