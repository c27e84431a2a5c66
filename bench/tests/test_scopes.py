"""From the compiled step's HLO to the program's named scopes, and the
per-layer quantities that sum device time by scope."""
import pytest

from bench import scopes as S
from bench import trace as T


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/fed.oracle/vmap(fed.oracle_g)/jvp(transpose(jvp()))"
     "/dot_general", ("fed.oracle", "fed.oracle_g")),
    ("jit(f)/transpose(jvp(fed.oracle))/dot_general", ("fed.oracle",)),
    ("jit(f)/fed.oracle/vmap(fed.oracle_g)/jvp(fed.oracle_g)/mul",
     ("fed.oracle", "fed.oracle_g", "fed.oracle_g")),
    ("jit(f)/fed.reduce/cond/branch_1_fun/dynamic_update_slice",
     ("fed.reduce",)),
    ("jit(f)/fed.update/jit(storm3_step_flat)/pallas_call", ("fed.update",)),
    # JAX's own names, a reducer's region and no metadata name no scope
    ("jit(f)/reduce/add", ()),
    ("jit(fed)/vmap(jvp())/while/body/mul", ()),
    ("reduce_sum", ()),
    ("", ()),
])
def test_scopes_of_an_op_name(op_name, want):
    assert S.scopes(op_name) == want
    assert S.top(want) == (want[0] if want else None)


def test_unwrap_strips_every_transformation():
    assert S.unwrap("transpose(jvp(fed.oracle))") == "fed.oracle"
    assert S.unwrap("vmap(jvp())") == ""
    assert S.unwrap("jit(train_step)") == "train_step"
    assert S.unwrap("fed.flatten") == "fed.flatten"


def meta(op_name):
    return f', metadata={{op_name="{op_name}" source_file="a.py"}}'


ORACLE = "jit(step)/fed.oracle/vmap(jvp())/while/body/dot_general"
ORACLE_G = "jit(step)/fed.oracle/vmap(fed.oracle_g)/jvp(transpose(jvp()))/mul"

#: a compiled step in the shape the TPU compiler prints it: fusions with
#: their roots' metadata, a fusion without any (its fused root's counts), a
#: loop and a conditional around scoped bodies, ops with no metadata in
#: their bodies (the loop's or branch's scope counts) and at the top level
HLO = f"""HloModule jit_train_step

%fused_computation.1 (p: bf16[8,8]) -> bf16[8,8] {{
  %p = bf16[8,8]{{1,0}} parameter(0)
  ROOT %convolution.1 = bf16[8,8]{{1,0}} convolution(%p, %p), dim_labels=bf_io->bf{meta(ORACLE)}
}}

%fused_computation.2 (p: f32[16]) -> f32[16] {{
  %p.1 = f32[16]{{0}} parameter(0)
  ROOT %multiply.4 = f32[16]{{0}} multiply(%p.1, %p.1){meta(ORACLE_G)}
}}

%body.3 (t: (s32[], bf16[8,8])) -> (s32[], bf16[8,8]) {{
  %t = (s32[], bf16[8,8]{{1,0}}) parameter(0)
  %gte.1 = bf16[8,8]{{1,0}} get-tuple-element(%t), index=1
  %convolution_fusion.1 = bf16[8,8]{{1,0}} fusion(%gte.1), kind=kOutput, calls=%fused_computation.1{meta(ORACLE)}
  %multiply_fusion.2 = f32[16]{{0}} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2
  %copy.5 = bf16[8,8]{{1,0}} copy(%gte.1)
  ROOT %tuple.1 = (s32[], bf16[8,8]{{1,0}}) tuple(%gte.1, %convolution_fusion.1)
}}

%branch.4 (b: f32[16]) -> f32[16] {{
  %b = f32[16]{{0}} parameter(0)
  %copy.6 = f32[16]{{0}} copy(%b)
  ROOT %dynamic-update-slice.9 = f32[16]{{0}} dynamic-update-slice(%b, %b, %b){meta("jit(step)/fed.reduce/cond/branch_1_fun/dynamic_update_slice")}
}}

ENTRY %main.5 (a: f32[16]) -> f32[16] {{
  %a = f32[16]{{0}} parameter(0)
  %dynamic-slice.2 = bf16[8,8]{{1,0}} dynamic-slice(%a), dynamic_slice_sizes={{8,8}}{meta("jit(step)/fed.unflatten/slice")}
  %while.6 = (s32[], bf16[8,8]{{1,0}}) while(%dynamic-slice.2), condition=%cond.9, body=%body.3{meta("jit(step)/fed.oracle/vmap(jvp())/while")}
  %reshape.7 = f32[16]{{0}} reshape(%a){meta("jit(step)/fed.flatten/concatenate")}
  %storm3_step_flat.1 = (bf16[16]{{0}}, f32[16]{{0}}) custom-call(%a), custom_call_target="tpu_custom_call"{meta("jit(step)/fed.update/jit(storm3_step_flat)/pallas_call")}
  %conditional.8 = f32[16]{{0}} conditional(%a), branch_computations={{%branch.4}}{meta("jit(step)/fed.reduce/cond")}
  %copy.3 = f32[16]{{0}} copy(%a)
  ROOT %add.1 = f32[16]{{0}} add(%copy.3, %a){meta("jit(step)/add")}
}}
"""


def test_scope_map_fusions_loops_and_bare_ops():
    m = S.scope_map(HLO)
    assert m["%convolution_fusion.1"] == ("fed.oracle",)
    # no metadata of its own: the fused root's
    assert m["%multiply_fusion.2"] == ("fed.oracle", "fed.oracle_g")
    assert m["%dynamic-slice.2"] == ("fed.unflatten",)
    assert m["%reshape.7"] == ("fed.flatten",)
    assert m["%storm3_step_flat.1"] == ("fed.update",)
    assert m["%dynamic-update-slice.9"] == ("fed.reduce",)
    # no metadata: the scope of the loop or branch that runs them ...
    assert m["%copy.5"] == m["%gte.1"] == ("fed.oracle",)
    assert m["%copy.6"] == ("fed.reduce",)
    # ... and none at the top level
    assert m["%copy.3"] == () and m["%add.1"] == () and m["%a"] == ()


def synthetic():
    """One device, two steps, no two ops overlapping except a loop and a
    conditional over their bodies' ops (ns)."""
    dev = [T.Op("%dynamic-slice.2", 100, 10, "dynamic-slice"),
           T.Op("%while.6", 110, 60, "while"),
           T.Op("%convolution_fusion.1", 112, 20, "fusion/kOutput"),
           T.Op("%multiply_fusion.2", 132, 30, "fusion/kLoop"),
           T.Op("%copy.5", 162, 6, "copy"),
           T.Op("%reshape.7", 170, 5, "reshape"),
           T.Op("%storm3_step_flat.1", 175, 15, "custom-call"),
           T.Op("%conditional.8", 190, 12, "conditional"),
           T.Op("%dynamic-update-slice.9", 191, 8, "dynamic-update-slice"),
           T.Op("%copy.6", 199, 2, "copy"),
           T.Op("%copy.3", 202, 4, "copy"),
           T.Op("%add.1", 206, 2, "add"),
           T.Op("%not-in-the-hlo.1", 208, 2, "fusion/kLoop")]
    host = [T.Op("dispatch", 90, 5, "host"), T.Op("drain", 200, 20, "host")]
    return {"devices": {"/device:TPU:0": sorted(dev, key=T.by_start)},
            "host": sorted(host, key=T.by_start)}


def read(name, trace, hlo=HLO):
    """What a reader of the metric ``name`` returns."""
    return S.ms(trace, hlo, S.METRICS[name])


def test_readers_sum_device_time_by_scope():
    t = T.Trace(synthetic(), steps=2)
    read_ = lambda name: read(name, t)
    assert read_("oracle.ms") == pytest.approx((20 + 30 + 6) / 2 / 1e6)
    assert read_("oracle.g_ms") == pytest.approx(30 / 2 / 1e6)
    assert read_("flat.boundary_ms") == pytest.approx((10 + 5) / 2 / 1e6)
    assert read_("reduce.ms") == pytest.approx((8 + 2) / 2 / 1e6)
    # the copy, the add and an op the HLO does not name
    assert read_("step.unscoped_ms") == pytest.approx((4 + 2 + 2) / 2 / 1e6)


def test_top_level_buckets_partition_the_busy_time():
    t = T.Trace(synthetic(), steps=2)
    buckets = [S.ms(t, HLO, lambda p, s=s: S.top(p) == s) for s in S.TOP]
    total = sum(b for b in buckets if b) + read("step.unscoped_ms", t)
    # the loop's own time outside its body's ops (2 + 2 ns) and the
    # conditional's (1 + 1 ns) are busy but in no op
    assert total == pytest.approx((t.busy_ns() - 6) / 2 / 1e6)
    assert S.ms(t, HLO, lambda p: True) == pytest.approx(total)


def test_readers_are_silent_without_scopes():
    t = T.Trace(synthetic(), steps=2)
    bare = HLO.replace("fed.", "x")
    assert set(S.METRICS) == {"oracle.ms", "oracle.g_ms", "flat.boundary_ms",
                              "reduce.ms", "step.unscoped_ms"}
    for name in S.METRICS:
        assert read(name, t, None) is None
        assert read(name, t, bare) is None
    # a scope that ran nothing reads as nothing
    assert S.ms(t, HLO, lambda p: "fed.missing" in p) is None


def test_recorded_step_by_scope():
    """Two slices of a mamba step recorded on a TPU v5e (``test_trace``),
    attributed with an excerpt of the same step's compiled HLO.  The loop
    after the STORM launch, which relays the bf16 variables out of the
    kernel's 1-D layout into the [M, N] one the reduction reads, is XLA's:
    its ops carry no metadata and lie under no scope.  The slice of the
    oracle's scan lies under ``fed.oracle`` but for a few ops XLA added
    outside the loop."""
    import gzip
    import os
    from bench.tests.test_trace import recorded
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_step_hlo_excerpt.txt.gz")
    with gzip.open(path, "rt") as fh:
        hlo = fh.read()
    launch, scan = recorded()
    t = T.Trace(launch, steps=1)
    read_ = lambda name, tr=t: read(name, tr, hlo)
    ops = {o.name: o for o in launch["devices"]["/device:TPU:0"]}
    dur = lambda *names: sum(o.dur for o in launch["devices"]["/device:TPU:0"]
                             if o.name in names) / 1e6
    assert S.ms(t, hlo, lambda p: S.top(p) == "fed.update") == pytest.approx(
        ops["%storm3_step_flat.2"].dur / 1e6)
    assert read_("reduce.ms") == pytest.approx(dur(
        "%convert_reduce_fusion", "%multiply_convert_fusion", "%copy-start.208"))
    relayout = ("%dynamic-slice.153", "%reshape.10313",
                "%dynamic-update-slice.237")
    assert read_("step.unscoped_ms") == pytest.approx(dur(
        *relayout, "%broadcast.7470", "%copy-done.191", "%copy-done.192"))
    assert dur(*relayout) > 0.9 * ops["%while.647"].dur / 1e6
    assert read_("oracle.ms") is None and read_("flat.boundary_ms") is None
    t = T.Trace(scan, steps=1)
    assert read_("oracle.ms", t) > 0.9 * t.busy_ns() / 1e6
