"""Trace reduction: the loader on a trace recorded here on the CPU, and the
reduction on a small recorded chip trace (``trace_fixture.json``, cut from
a traced run of ``sc2-asha-scan`` on one TPU v5e) with its numbers worked
out by hand from the fixture's events."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import trace as TR  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_fixture.json")


def test_loader_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation(TR.SPAN_PREFIX + "proposer"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = TR.load(TR.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in t["host"]]
    assert TR.WINDOW_SPAN in names and TR.SPAN_PREFIX + "proposer" in names
    w0, w1 = TR.window_bounds(t)
    assert w1 > w0


def _synthetic():
    # window 0..100 ns; device ops 10-30, 20-40 (overlap), 60-70; a host
    # span covers 40-60, so the gap 40-60 is its and 0-10, 70-100 are not
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 10, 20], ["fusion.2", 20, 20],
                    ["custom-call.3", 60, 10]],
            "modules": [["jit_scan_chunk(7)", 10, 60]]}},
        "host": [[TR.WINDOW_SPAN, 0, 100],
                 [TR.SPAN_PREFIX + "proposer", 35, 30]],
    }


def test_reduce_busy_gaps_and_ops_on_a_synthetic_trace():
    red = TR.reduce(_synthetic())
    assert abs(red["window_s"] - 100e-9) < 1e-15
    assert abs(red["busy_s"] - 40e-9) < 1e-15
    assert red["ops"]["fusion.1"][0] == 1
    assert abs(red["ops"]["fusion.1"][1] - 20e-9) < 1e-15
    assert red["modules"]["jit_scan_chunk(7)"][0] == 1
    assert abs(red["modules"]["jit_scan_chunk(7)"][1] - 60e-9) < 1e-15
    gaps = red["idle_by_span_s"]
    assert abs(gaps["proposer"] - 20e-9) < 1e-15
    assert abs(gaps["unattributed"] - 40e-9) < 1e-15
    b = TR.breakdown(red)
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0][0] == "unattributed"


def test_reduce_a_recorded_chip_trace():
    """60 ms of a traced ``sc2-asha-scan`` run: the end of a lane op and the
    first forward pass of a fused scan chunk (two flash-attention calls)."""
    from chipbench import kernels as K

    with open(FIXTURE) as f:
        t = json.load(f)
    red = TR.reduce(t)
    ops = t["devices"]["/device:TPU:0"]["ops"]
    w0, w1 = TR.window_bounds(t)
    # busy by a sweep over event edges, independent of the reduction's union
    edges = sorted([(max(s, w0), 1) for _, s, d in ops if s + d > w0 and s < w1]
                   + [(min(s + d, w1), -1) for _, s, d in ops
                      if s + d > w0 and s < w1])
    busy, depth, last = 0.0, 0, w0
    for x, step in edges:
        if depth > 0:
            busy += x - last
        depth, last = depth + step, x
    assert abs(red["window_s"] - 0.06) < 1e-9
    assert abs(red["busy_s"] - busy * 1e-9) < 1e-9
    calls, secs = K.events(red, "flash_attention")
    assert calls == 2 and abs(secs - (377602 + 377291) * 1e-9) < 1e-12
    calls, _ = K.events(red, "rmsnorm")
    assert calls == 4
    assert TR.op_kind("vmap_jvp_jit_rmsnorm_pallas___.12 f32[2,1024,3072]") \
        == "vmap_jvp_jit_rmsnorm_pallas___"
    top = TR.breakdown(red)["device_ops"]
    assert top[0][0] == "fusion.9 (tuple)" and len(top) == 10
