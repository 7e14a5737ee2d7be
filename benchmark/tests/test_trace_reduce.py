"""The trace reduction, on a small trace recorded on a v5e (PR 23, my chip
run: tiny-test through the engine, 0.25 s traced, one fused step in it) and
on hand-made planes."""

import gzip
import os
import types

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import (device_idle_share, host_per_step,
                               module_time)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rb") as f:
        return tr.reduce_planes(
            ProfileData.from_serialized_xspace(f.read()).planes)


def test_recorded_trace_window_busy_and_modules(recorded):
    r = recorded
    assert r.devices == 1
    assert list(r.host) == ["bench.engine_step"]
    assert r.window_s == pytest.approx(0.004331801, rel=1e-6)
    assert list(r.modules) == ["jit__threefry_split", "jit__unstack",
                               "jit__paged_fused_step"]
    (start, dur), = r.modules["jit__paged_fused_step"]
    assert dur == 92361.0
    # busy: the union of the op intervals; it cannot pass the programs' time
    assert r.busy_s == pytest.approx(9.4313e-05, rel=1e-4)
    assert r.busy_s <= sum(d for v in r.modules.values()
                           for _s, d in v) / 1e9


def test_recorded_trace_ops_are_self_times_and_add_up(recorded):
    r = recorded
    # the layer scan is a `while` whose body's ops are nested in it: its own
    # time is small, and the table adds up to the busy time
    assert sum(r.ops.values()) == pytest.approx(r.busy_s, rel=1e-3)
    top = dict(tr.top(r.ops, 10))
    assert "sort.1_f32_64_512_" in top
    assert r.ops["while.5_s32_"] < 2e-05


def test_recorded_trace_gaps_and_readers(recorded):
    r = recorded
    idle = r.window_s - r.busy_s
    assert sum(r.gaps.values()) == pytest.approx(idle, rel=0.02)
    rec = types.SimpleNamespace(trace=r)
    assert module_time.read(rec, {"module": "paged_fused_step"}) == \
        pytest.approx(0.092361)
    assert device_idle_share.read(rec, {}) == pytest.approx(
        100 * (1 - r.busy_s / r.window_s))
    host = host_per_step.read(rec, {"annotation": "bench.engine_step",
                                    "module": "paged_fused_step"})
    assert 0.0 < host < 1e3 * r.window_s
    assert module_time.read(types.SimpleNamespace(trace=None),
                            {"module": "x"}) is None


def _plane(name, lines):
    def ev(n, s, d):
        return types.SimpleNamespace(name=n, start_ns=s, duration_ns=d,
                                     stats=[])
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
        for ln, evs in lines])


def test_hand_made_planes():
    planes = [
        _plane("/host:CPU", [("main", [("bench.engine_step", 0, 1000),
                                       ("Enqueue", 100, 50),
                                       ("bench.engine_step", 1000, 1000)])]),
        _plane("/device:TPU:0", [
            ("XLA Modules", [("jit_step(1)", 100, 500),
                             ("jit_step(1)", 1200, 300)]),
            ("XLA Ops", [("%while.1 = s32[] while(...)", 100, 500),
                         ("%fusion.1 = bf16[8,4]{1,0} fusion(...)", 100, 200),
                         ("%fusion.1 = bf16[8,4]{1,0} fusion(...)", 350, 200),
                         ("%copy.2 = f32[2]{0} copy(...)", 1200, 300)])]),
        _plane("/device:TPU:1", [
            ("XLA Ops", [("%copy.2 = f32[2]{0} copy(...)", 0, 1000)])])]
    r = tr.reduce_planes(planes, min_gap_ns=50)
    assert r.window_ns == (0, 2000) and r.devices == 2
    # chip 0: [100,600] and [1200,1500] = 800 ns; chip 1: 1000 ns
    assert r.busy_s == pytest.approx((800 + 1000) / 2 / 1e9)
    assert r.ops == {"while.1_s32_": pytest.approx(100e-9),
                     "fusion.1_bf16_8_4_": pytest.approx(400e-9),
                     "copy.2_f32_2_": pytest.approx(300e-9)}
    assert r.modules == {"jit_step": [(100, 500), (1200, 300)]}
    # gaps of chip 0: [0,100] under Enqueue? no: its middle, 50, lies in the
    # first engine step only; [600,1200] and [1500,2000] likewise
    assert r.gaps == {"bench.engine_step": pytest.approx(1200e-9)}
