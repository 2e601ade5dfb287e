"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at the smoke
preset's widths (the same code path the chip runs at published widths), and
its refusal to report success off a TPU."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve annotations here
    spec.loader.exec_module(mod)
    mod._install_setup_clock()
    return mod


def _smoke(mod, lanes):
    # the starcoder2 preset itself: smoke widths, same depth and dtypes
    return mod.Geometry(overrides={}, batch=2, seq=16, steps=3, lanes=lanes)


def test_published_geometry_cuts_depth_only(chip_smoke):
    from repro.configs import get_config

    geo = chip_smoke.published_geometry(lanes=2)
    full = get_config("starcoder2-3b")
    assert geo.overrides["n_layers"] == 2
    for f in chip_smoke.WIDTH_FIELDS:
        assert geo.overrides[f] == getattr(full, f)
    assert (geo.overrides["d_model"], geo.overrides["vocab_size"]) == (3072, 49152)
    assert geo.seq == 1024 and chip_smoke.REDUCED == ["n_layers 30 -> 2"]


def test_phase_batch_matches_serial_twin(chip_smoke):
    r = chip_smoke.phase_batch(_smoke(chip_smoke, 2))
    assert r["phase"] == "A-batch"
    assert len(r["lane_losses"]) == 2
    assert r["max_rel_diff"] <= chip_smoke.REL_TOL
    assert r["steady_step_s"] > 0 and r["setup_s"] > 0
    # the CPU runs the reference ops at smoke widths: no kernel expected
    assert r["tpu_custom_call"] is False


def test_phase_stream_streams_every_job(chip_smoke):
    r = chip_smoke.phase_stream(_smoke(chip_smoke, 2))
    assert r["sampled_configs"] == 4
    assert r["streamed_results"] == r["jobs"] >= 4
    assert r["lane_refills"] >= 1
    assert r["flight_deaths"] == r["quarantined"] == 0
    assert len(r["losses"]) == r["jobs"]


def test_phase_four_chips_width2_matches_width1(chip_smoke):
    r = chip_smoke.phase_four_chips(_smoke(chip_smoke, 4))
    assert r["width1"]["model_axis_collectives"] == 0
    assert r["width2"]["model_axis_collectives"] > 0
    assert r["width2"]["max_rel_diff_vs_width1"] <= chip_smoke.REL_TOL


def test_phase_failure_is_loud(chip_smoke):
    class Job:
        job_id, retries, result = 0, 1, None
        status = __import__("repro.core.job", fromlist=["JobStatus"]).JobStatus.FINISHED

    class Exp:
        job_log = [Job()]

    with pytest.raises(chip_smoke.PhaseFailed, match="retried"):
        chip_smoke._settled_jobs(Exp())


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            # a fixed path inside the checkout, the same on every run
            assert got == os.path.join(os.path.abspath(ROOT), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            # JAX reads the variable itself; nothing is set in code
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_main_refuses_without_a_tpu(chip_smoke, capsys):
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    for line in out.splitlines():
        try:
            assert "ok" not in json.loads(line)
        except ValueError:
            pass
