"""The reduction from a profiler trace to the per-layer metrics."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench import trace as T
from bench.run import _module

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def synthetic():
    """Two steps on one device: a 30 ns matmul, a 10 ns STORM kernel and a
    20 ns fusion overlapping the matmul, with idle time between."""
    dev = [T.Op("%fusion.1", 105, 20, "fusion/kLoop"),
           T.Op("%convolution.3", 100, 30, "convolution"),
           T.Op("%storm3_step_flat.2", 140, 10, "custom-call"),
           T.Op("%convolution_add_fusion.3", 200, 30, "fusion/kOutput"),
           T.Op("%reshape.5", 240, 10, "reshape"),
           T.Op("%storm3_step_flat.2", 240, 10, "custom-call"),
           T.Op("%early", 10, 50, "fusion/kLoop")]
    host = [T.Op("dispatch", 90, 5, "host"), T.Op("wait", 160, 30, "host"),
            T.Op("dispatch", 190, 7, "host"), T.Op("drain", 250, 50, "host")]
    return {"devices": {"/device:TPU:0": sorted(dev, key=T.by_start)},
            "host": sorted(host, key=T.by_start)}


def test_parse_names_an_op_by_its_hlo_text():
    assert T.parse("%fusion.13 = bf16[2048,768]{1,0:T(8,128)(2,1)S(1)} "
                   "fusion(bf16[2,50280,768]{2,1,0:T(8,128)(2,1)} %c), "
                   "kind=kOutput, calls=%f") == ("%fusion.13", "fusion/kOutput")
    assert T.parse("%storm3_step_flat.3 = (f32[131072]{0:T(1024)S(1)}, "
                   "f32[131072]{0:T(1024)}) custom-call(f32[2,1,1] %b)") == (
        "%storm3_step_flat.3", "custom-call")
    assert T.parse("%while.6 = (s32[]{:T(128)}, bf16[2]) while((s32[]) %t)"
                   ) == ("%while.6", "while")


def test_top_ops_group_instances_and_skip_containers():
    raw = synthetic()
    raw["devices"]["/device:TPU:0"].append(T.Op("%while.1", 95, 200, "while"))
    top = dict(T.Trace(raw, steps=2).top_ops())
    assert top["custom-call %storm3_step_flat"] == 20
    assert not any(k.startswith("while") for k in top)


def test_union():
    assert T.union([(0, 10), (5, 12), (20, 25)]) == 17
    assert T.union([]) == 0


def test_window_busy_and_idle():
    t = T.Trace(synthetic(), steps=2)
    assert (t.t0, t.t1) == (90, 300)
    # "early" ends before the window and is dropped
    assert [o.name for o in t.devices["/device:TPU:0"]][0] == "%convolution.3"
    assert t.busy_ns() == 30 + 10 + 30 + 10
    idle = _module("layers", "device.idle_share").read(t, {})
    assert idle == pytest.approx(100 * (1 - 80 / 210))
    gaps = t.idle_gaps()
    # 250..300 under drain, 150..200 under wait, then 130..140 (10 ns)
    assert set(gaps[:2]) == {("drain", 50), ("wait", 50)}
    assert gaps[2][1] == 10


HLO = """HloModule jit_train_step

%fused_computation.7 (p: bf16[8,8], q: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  %q = bf16[8,8]{1,0} parameter(1)
  %convolution.1 = bf16[8,8]{1,0} convolution(%p, %q), dim_labels=bf_io->bf
  ROOT %add.2 = bf16[8,8]{1,0} add(%convolution.1, %p)
}

%fused_computation.8 (p: bf16[8]) -> bf16[8] {
  ROOT %p = bf16[8]{0} parameter(0)
}

ENTRY %main.3 (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %convolution_add_fusion.3 = bf16[8,8]{1,0} fusion(%a, %a), kind=kOutput, calls=%fused_computation.7
  %bitcast_add_fusion.1 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%fused_computation.8
  ROOT %convolution.3 = bf16[8,8]{1,0} convolution(%a, %a), dim_labels=bf_io->bf
}
"""


def test_matmul_ops_from_compiled_hlo():
    assert T.matmul_ops(HLO) == {"%convolution.1", "%convolution.3",
                                 "%convolution_add_fusion.3"}


def test_layer_readers():
    t = T.Trace(synthetic(), steps=2)
    ctx = {"steps": 2, "chips": 1, "peak": PEAK, "flops_per_step": 21e3,
           "storm_bytes_per_step": 5.0,
           "matmul_ops": {"%convolution.3", "%convolution_add_fusion.3"}}
    read = lambda name: _module("layers", name).read(t, ctx)
    assert read("oracle.mxu_ms") == pytest.approx(30e-6)
    assert read("storm.ms") == pytest.approx(10e-6)
    # 5 B at 1 GB/s take 5 ns of the 10 ns the kernel ran
    assert read("storm_roofline") == pytest.approx(50.0)
    assert read("host.dispatch_ms") == pytest.approx(6e-6)
    assert read("step.mfu") == pytest.approx(100 * 42e3 / (210e-9 * 1e12))


def test_readers_find_nothing_and_say_so():
    raw = synthetic()
    raw["devices"] = {}
    t = T.Trace(raw, steps=2)
    for name in ("device.idle_share", "oracle.mxu_ms", "storm.ms",
                 "storm_roofline"):
        assert _module("layers", name).read(
            t, {"peak": PEAK, "matmul_ops": {"%convolution.3"}}) is None


def test_load_reads_a_recorded_trace(tmp_path):
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name in ("dispatch", "wait", "dispatch", "drain"):
            with TraceAnnotation(name):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    raw = T.load(str(tmp_path))
    assert [h.name for h in raw["host"]] == ["dispatch", "wait", "dispatch",
                                            "drain"]
    t = T.Trace(raw, steps=2)
    assert t.window_ns > 0


def recorded():
    """Two slices of a step recorded on a TPU v5e (``tests/data``)."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_slices.json.gz")
    with gzip.open(path, "rt") as fh:
        data = json.load(fh)
    return [{"devices": {"/device:TPU:0": [T.Op(*o) for o in s["ops"]]},
             "host": [T.Op(n, a, d, "host") for n, a, d in s["host"]]}
            for s in data["slices"]]


def test_recorded_storm_launch():
    raw = recorded()[0]
    t = T.Trace(raw, steps=1)
    storm = [o for o in raw["devices"]["/device:TPU:0"]
             if o.name.startswith("%storm3_step_flat")]
    assert len(storm) == 1 and storm[0].dur > 1e7     # ~10.7 ms
    ctx = {"steps": 1, "peak": {"hbm_bytes_per_s": 819e9},
           "storm_bytes_per_step": 412_745_728 * 16}
    assert _module("layers", "storm.ms").read(t, ctx) == storm[0].dur / 1e6
    roof = _module("layers", "storm_roofline").read(t, ctx)
    assert roof == pytest.approx(100 * 412_745_728 * 16 / 819e9
                                 / (storm[0].dur / 1e9))
    assert 50 < roof < 100


def test_recorded_oracle_slice():
    import numpy as np
    raw = recorded()[1]
    ops = raw["devices"]["/device:TPU:0"]
    t = T.Trace(raw, steps=1)
    # busy time by painting every nanosecond an op covers
    painted = np.zeros(int(t.t1 - t.t0) + 1, bool)
    for o in ops:
        painted[int(o.start - t.t0):int(o.end - t.t0)] = True
    assert t.busy_ns() == pytest.approx(painted.sum(), abs=len(ops))
    # the reader sums the device time of exactly the ops it is told multiply
    # matrices (here: the slice's output fusions), overlaps counted once
    mm = [o for o in ops if o.category == "fusion/kOutput"]
    assert len(mm) > 20
    ctx = {"matmul_ops": {o.name for o in mm}}
    assert _module("layers", "oracle.mxu_ms").read(t, ctx) == pytest.approx(
        sum(o.dur for o in mm) / 1e6)
    assert _module("layers", "storm.ms").read(t, {}) is None
