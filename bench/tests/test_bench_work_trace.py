"""Work counts pinned to the paper's Fig. 9 programs, and the trace
reduction and per-layer readers on a small synthetic trace and on a
recorded CPU trace."""

import glob
import os

import jax
import pytest

from bench import harness
from bench import trace as btrace

EW = harness.load_family("elementwise")

FIG9 = {  # op, dtype -> NOR gates, in bits, out bits
    ("add", "uint32"): (352, 64, 33),
    ("mul", "uint32"): (12880, 64, 64),
    ("div", "uint32"): (17590, 96, 64),
    ("fp_add", "float32"): (3719, 64, 32),
    ("fp_mul", "float32"): (8703, 64, 32),
    ("fp_div", "float32"): (12295, 64, 32),
}


@pytest.mark.parametrize("op,dtype", sorted(FIG9))
def test_fig9_work(op, dtype):
    w = EW.op_work(op, dtype, "bit-serial")
    assert (w.nor_gates, w.in_bits, w.out_bits) == FIG9[op, dtype]
    assert w.port_bytes == (w.in_bits + w.out_bits) / 8


MS = 1_000_000      # ns


def synthetic() -> btrace.Raw:
    """A 100 ms window: prepare 0-20, dispatch 20-80, finish 80-100 on the
    host; chip 0 runs ops 30-40 and 50-70 (two executables), chip 1 runs
    one op 30-60."""
    spans = [("bench.window", 0, 100 * MS),
             ("bench.prepare:fp_add", 0, 20 * MS),
             ("bench.dispatch:fp_add", 20 * MS, 80 * MS),
             ("bench.finish:fp_add", 80 * MS, 100 * MS)]
    ops = {0: [("fusion.1", 30 * MS, 40 * MS), ("while.2", 50 * MS, 70 * MS),
               ("fusion.3", 55 * MS, 60 * MS)],       # nested in the while
           1: [("while.2", 30 * MS, 60 * MS)]}
    modules = {0: [("jit_pim_exec_ref_slots_fused(1)", 30 * MS, 40 * MS),
                   ("jit_concatenate(2)", 50 * MS, 55 * MS),
                   ("jit_pim_exec_ref_slots_fused(1)", 55 * MS, 70 * MS)],
               1: [("jit_pim_exec_ref_slots_fused(1)", 30 * MS, 60 * MS)]}
    return btrace.Raw(spans=spans, ops=ops, modules=modules)


def test_interval_helpers():
    assert btrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert btrace.gaps([(2, 3), (5, 9)], 0, 8) == [(0, 2), (3, 5)]
    assert btrace.overlap([(0, 4), (6, 10)], [(2, 8)]) == 4


def test_reduce_synthetic():
    r = btrace.reduce(synthetic())
    assert r.window_s == pytest.approx(0.1)
    assert r.chips == 2
    # chip 0 busy 30 ms, chip 1 busy 30 ms
    assert r.busy_s == pytest.approx(0.030)
    assert r.span_s == pytest.approx(
        {"prepare": 0.02, "dispatch": 0.06, "finish": 0.02})
    # dispatch 20-80; some chip busy 30-70 -> host-only 20 ms
    assert r.dispatch_host_s == pytest.approx(0.020)
    assert r.executor_s == pytest.approx(0.055)
    assert r.kernels == pytest.approx({"jit_pim_exec_ref_slots_fused(1)":
                                       0.055, "jit_concatenate(2)": 0.005})
    # chip 0 idle: 0-30 (prepare 20, dispatch 10), 40-50, 70-100
    # (dispatch 10, finish 20); chip 1 idle: 0-30, 60-100
    assert r.idle_by_span == pytest.approx({
        "bench.prepare:fp_add": 0.020,
        "bench.dispatch:fp_add": (0.030 + 0.030) / 2,
        "bench.finish:fp_add": 0.020})
    b = r.breakdown()
    assert b["device_ops"][0] == ["while.2", pytest.approx(0.050)]
    assert len(b["idle_gaps"]) == 3


def test_readers_on_synthetic_trace():
    r = btrace.reduce(synthetic())
    w = EW.op_work("fp_add", "float32", "bit-serial")
    readings = harness.Readings(
        setup_s=1.0, calls=[harness.Call("fp_add", 1000, 0, 0.1)],
        window_s=r.window_s, work={"fp_add": w},
        peaks={"hbm_bytes_per_s": 819e9}, trace=r)
    read = {m: harness.load_metric(m)(readings) for m in (
        "frontend_pct", "dispatch_host_pct", "pim_exec_roofline",
        "gate_rows_per_dev_s", "device_idle_pct", "rows_per_s",
        "call_p95_s", "setup_s")}
    assert read["frontend_pct"] == pytest.approx(40.0)
    assert read["dispatch_host_pct"] == pytest.approx(20.0)
    assert read["device_idle_pct"] == pytest.approx(70.0)
    assert read["pim_exec_roofline"] == pytest.approx(
        100 * 1000 * 12 / 819e9 / 0.055)
    assert read["gate_rows_per_dev_s"] == pytest.approx(3719 * 1000 / 0.055)
    # end-to-end readers say nothing in a traced run
    assert read["rows_per_s"] is read["call_p95_s"] is read["setup_s"] is None


def test_readers_find_nothing_without_a_trace():
    readings = harness.Readings(
        setup_s=2.5,
        calls=[harness.Call("add", 100, 0, 0.5),
               harness.Call("mul", 100, 1, 1.5)],
        window_s=2.0, work={}, peaks={})
    assert harness.load_metric("rows_per_s")(readings) == 100.0
    assert harness.load_metric("setup_s")(readings) == 2.5
    assert harness.load_metric("call_p95_s")(readings) == \
        pytest.approx(0.5 + 0.95 * 1.0)
    for m in ("frontend_pct", "dispatch_host_pct", "pim_exec_roofline",
              "gate_rows_per_dev_s", "device_idle_pct"):
        assert harness.load_metric(m)(readings) is None


def test_spans_from_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda v: v * 3 + 1)
    x = jax.numpy.ones(64)
    f(x).block_until_ready()
    with harness._profiled(jax, str(tmp_path)):
        with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.dispatch:add"):
                f(x).block_until_ready()
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    raw = btrace.load(path)
    names = sorted(n for n, _, _ in raw.spans)
    assert names == ["bench.dispatch:add", "bench.window"]
    assert raw.ops == {} and raw.modules == {}       # no TPU planes here
    with pytest.raises(ValueError, match="no device events"):
        btrace.reduce(raw)


def test_nested_ops_are_not_counted_twice():
    """A while loop and the fusions of each of its iterations are all op
    events; busy and kernel time come from the executable's one event."""
    spans = [("bench.window", 0, 100 * MS),
             ("bench.dispatch:fp_mul", 0, 100 * MS)]
    ops = {0: [("while.7", 10 * MS, 90 * MS)] +
              [(f"fusion.{k % 3}", (10 + 8 * k) * MS, (18 + 8 * k) * MS)
               for k in range(10)]}
    modules = {0: [("jit_pim_exec(7)", 10 * MS, 90 * MS)]}
    r = btrace.reduce(btrace.Raw(spans=spans, ops=ops, modules=modules))
    assert r.busy_s == pytest.approx(0.080)
    assert r.executor_s == pytest.approx(0.080)
    assert sum(r.device_ops.values()) == pytest.approx(0.160)
    assert r.dispatch_host_s == pytest.approx(0.020)
    with pytest.raises(ValueError, match="no executable events"):
        btrace.reduce(btrace.Raw(spans=spans, ops=ops, modules={}))


def test_a_trace_that_dropped_events_is_refused():
    assert btrace.dropped([("device_id", 0), ("dropped_events", 0)]) == []
    assert btrace.dropped([("Dropped Traces", 12), ("x", 5)]) == \
        ["Dropped Traces=12"]
    assert btrace.dropped([("dropped", "unknown")]) == ["dropped=unknown"]
