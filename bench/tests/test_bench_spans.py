"""The reduction of the program's own ``pim.*`` spans (``bench/spans.py``)
and the shares of the window it gives: on a small synthetic trace, and on
a trace recorded on the CPU from real ufunc calls; and the harness's own
traced run, which leaves the program's tracer off."""

import glob
import os

import jax
import numpy as np
import pytest

from bench import harness
from bench import spans as bspans
from bench import trace as btrace

MS = 1_000_000      # ns

def synthetic():
    """A 100 ms window of one fp_add of two chunks: the harness's spans,
    the program's spans inside them, chip 0 busy 30-40 and 50-70, chip 1
    busy 30-60."""
    raw = btrace.Raw(
        spans=[("bench.window", 0, 100 * MS),
               ("bench.prepare:fp_add", 0, 20 * MS),
               ("bench.dispatch:fp_add", 20 * MS, 80 * MS),
               ("bench.finish:fp_add", 80 * MS, 98 * MS)],
        ops={},
        modules={0: [("jit_pim_exec_ref_slots_fused(1)", 30 * MS, 40 * MS),
                     ("jit_pim_exec_ref_slots_fused(1)", 50 * MS, 70 * MS)],
                 1: [("jit_pim_exec_ref_slots_fused(1)", 30 * MS, 60 * MS)]})
    program = [(n, s * MS, e * MS, a) for n, s, e, a in [
        ("pim.prepare", 0, 18, {"op": "fp_add"}),
        ("pim.prepare.cast", 1, 5, {}),
        ("pim.prepare.check", 6, 16, {}),
        ("pim.prepare.bind", 16, 18, {}),
        ("pim.dispatch.pack", 20, 25, {"chunk": 0}),
        ("pim.dispatch.h2d", 25, 27, {"chunk": 0}),
        ("pim.dispatch.launch", 27, 30, {"chunk": 0}),
        ("pim.dispatch.pack", 30, 35, {"chunk": 1}),
        ("pim.dispatch.h2d", 35, 36, {"chunk": 1}),
        ("pim.dispatch.launch", 36, 37, {"chunk": 1}),
        ("pim.dispatch.wait", 37, 45, {"chunk": 0}),
        ("pim.dispatch.d2h", 45, 46, {"chunk": 0}),
        ("pim.dispatch.unpack", 46, 50, {"chunk": 0}),
        ("pim.dispatch.wait", 50, 70, {"chunk": 1}),
        ("pim.dispatch.d2h", 70, 71, {"chunk": 1}),
        ("pim.dispatch.unpack", 71, 76, {"chunk": 1}),
        ("pim.dispatch.concat", 76, 79, {}),
        ("pim.finish", 80, 96, {})]]
    return raw, program


def test_reduce_program_spans():
    raw, program = synthetic()
    p = bspans.reduce(raw, program)
    assert p.window_s == pytest.approx(0.1)
    assert p.seconds("pim.dispatch.wait") == pytest.approx(0.028)
    assert p.seconds("pim.prepare", "pim.prepare.cast") == \
        pytest.approx(0.018)                 # a union, not a sum
    assert p.seconds("pim.no.such.span") is None
    # each idle gap goes to the innermost span over it; chip 0 is idle
    # 0-30, 40-50, 70-100 and chip 1 0-30, 60-100: the mean of the two
    assert p.idle_by_span == pytest.approx({
        "pim.prepare": 0.002, "pim.prepare.cast": 0.004,
        "pim.prepare.check": 0.010, "pim.prepare.bind": 0.002,
        "bench.prepare:fp_add": 0.002,
        "pim.dispatch.pack": 0.005, "pim.dispatch.h2d": 0.002,
        "pim.dispatch.launch": 0.003,
        "pim.dispatch.wait": (0.005 + 0.010) / 2,
        "pim.dispatch.d2h": (0.002 + 0.001) / 2,
        "pim.dispatch.unpack": (0.009 + 0.005) / 2,
        "pim.dispatch.concat": 0.003, "bench.dispatch:fp_add": 0.001,
        "pim.finish": 0.016, "bench.finish:fp_add": 0.002,
        btrace.OUTSIDE: 0.002})
    assert sum(p.idle_by_span.values()) == pytest.approx(0.070)
    top = p.top_idle()
    assert len(top) == 10 and top[0] == ["pim.finish", pytest.approx(0.016)]


def test_program_span_shares():
    raw, program = synthetic()
    assert bspans.reduce(raw, program).shares() == pytest.approx({
        "prepare_cast_pct": 4.0, "prepare_check_pct": 10.0,
        "host_pack_pct": 10.0, "host_unpack_pct": 12.0,
        "transfer_host_pct": 5.0, "device_wait_pct": 28.0})
    # nothing to read: none of a share's spans, or no program spans
    no_check = bspans.reduce(raw, [s for s in program
                                   if s[0] != "pim.prepare.check"])
    assert "prepare_check_pct" not in no_check.shares()
    none = bspans.reduce(raw, [])
    assert none.shares() == {}
    assert none.idle_by_span == pytest.approx(
        btrace.reduce(raw).idle_by_span)


def test_innermost_and_split():
    pieces = bspans.innermost([("bench.x", 10, 50), ("pim.a", 20, 40),
                               ("pim.b", 25, 30)], 0, 60)
    assert pieces == [(0, 10, btrace.OUTSIDE), (10, 20, "bench.x"),
                      (20, 25, "pim.a"), (25, 30, "pim.b"),
                      (30, 40, "pim.a"), (40, 50, "bench.x"),
                      (50, 60, btrace.OUTSIDE)]
    assert bspans.split([(5, 22), (28, 33)], pieces) == {
        btrace.OUTSIDE: 5, "bench.x": 10, "pim.a": 5, "pim.b": 2}


def test_program_spans_from_a_recorded_cpu_trace(tmp_path):
    """Real ufunc calls with the tracer on, under the harness's profiler:
    an fp32 add streamed in three chunks (the fused path) and a uint32
    mul (the padded-io path) in one."""
    from repro import pim_ufunc as pim
    from repro.runtime import telemetry
    rng = np.random.default_rng(5)
    a = rng.uniform(1, 2, 150).astype(np.float32)
    b = rng.uniform(1, 2, 150).astype(np.float32)
    u = rng.integers(0, 1 << 32, 40, dtype=np.uint64).astype(np.uint32)
    v = rng.integers(0, 1 << 32, 40, dtype=np.uint64).astype(np.uint32)
    pim.fp_add(a, b, chunk_rows=64)             # compile outside the trace
    pim.mul(u, v)
    tracer = telemetry.TRACER
    with harness._profiled(jax, str(tmp_path)):
        tracer.enabled = True
        try:
            with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
                got = pim.fp_add(a, b, chunk_rows=64)
                prod = pim.mul(u, v)
        finally:
            tracer.enabled = False
            tracer.drain()
    np.testing.assert_array_equal(got, a + b)
    np.testing.assert_array_equal(prod, u.astype(np.uint64) * v)
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    raw = btrace.load(path)
    assert [n for n, _, _ in raw.spans] == [btrace.WINDOW_SPAN]
    program = bspans.load(path)
    names = [n for n, _, _, _ in sorted(program, key=lambda s: s[1])]
    for name in ("pim.prepare", "pim.prepare.cast", "pim.prepare.check",
                 "pim.prepare.bind", "pim.finish", "pim.dispatch.concat"):
        assert name in names, name
    chunks = {}
    for name, _, _, args in sorted(program, key=lambda s: s[1]):
        if name.startswith("pim.dispatch.") and name != \
                "pim.dispatch.concat":
            chunks.setdefault(name, []).append(args["chunk"])
    assert set(chunks) == {f"pim.dispatch.{s}" for s in (
        "pack", "h2d", "launch", "wait", "d2h", "unpack")}
    for name, seen in chunks.items():
        assert seen == [0, 1, 2, 0], name     # fp_add's three, mul's one
    assert names.count("pim.prepare.check") == 1   # fp only; mul: dtype
    prepares = [(s, e) for n, s, e, _ in program if n == "pim.prepare"]
    assert len(prepares) == 2
    for n, s, e, args in program:
        if n.startswith("pim.prepare."):
            assert any(ps <= s and e <= pe for ps, pe in prepares), n
            assert args["op"] in ("fp_add", "mul")
    p = bspans.reduce(raw, program)
    assert set(p.intervals) == set(names)
    assert p.idle_by_span == {}                  # no device planes here
    assert 0 < p.pct("pim.dispatch.pack") < 100


def _with_a_chip(monkeypatch):
    """``btrace.load`` with one executable event planted on a chip, which
    the CPU trace lacks; returns the list of paths it loaded."""
    load, paths = btrace.load, []

    def with_a_chip(path):
        paths.append(path)
        raw = load(path)
        ((lo, hi),) = [(s, e) for n, s, e in raw.spans
                       if n == btrace.WINDOW_SPAN]
        raw.modules = {0: [("jit_pim_exec_planted", lo + (hi - lo) / 4,
                            lo + (hi - lo) / 2)]}
        return raw
    monkeypatch.setattr(btrace, "load", with_a_chip)
    return paths


@pytest.mark.parametrize("name", ["fp32-fig9-64Mi", "int32-fig9-4Mi"])
def test_a_traced_run_of_the_harness_leaves_the_tracer_off(name,
                                                           monkeypatch):
    """The harness's traced path end to end on the CPU: the program's
    tracer is on for the profiled block only, so the block records the
    ``pim.*`` spans, the line gains their shares and the idle gaps by
    program span, and the tracer is off again after, its ring empty."""
    from repro.runtime import telemetry
    from bench.tests.test_bench_harness import drive, small_cell
    paths = _with_a_chip(monkeypatch)
    cell = small_cell(name)
    telemetry.TRACER.drain()
    line, _ = drive(cell, harness.Ufuncs(cell.config, cell.family),
                    traced=True)
    assert line["correct"], line["checks"]
    assert not telemetry.TRACER.enabled
    assert telemetry.TRACER.drain() == []
    assert len(paths) == 1
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps",
                                      "idle_gaps_by_program_span"}
    assert all(n.startswith("bench.") or n == btrace.OUTSIDE
               for n, _ in line["breakdown"]["idle_gaps"])
    assert any(n.startswith(bspans.PREFIX) for n, _ in
               line["breakdown"]["idle_gaps_by_program_span"])
    shares = set(bspans.SHARES) - (
        set() if name.startswith("fp32") else {"prepare_check_pct"})
    own = {"frontend_pct", "dispatch_host_pct", "pim_exec_roofline",
           "gate_rows_per_dev_s", "device_idle_pct"}
    assert own | set(bspans.SHARES) <= set(cell.per_layer)
    assert set(line["metrics"]) == own | shares
    assert all(0 < line["metrics"][n]["value"] < 100 for n in shares)


def test_a_traced_served_run_reads_the_server(monkeypatch):
    """The served path under the profiler on the CPU: the server's threads
    record their ``pim.*`` spans in the block, the line reads the queue's
    share of the latency and the program's shares, and the harness's own
    front-end and dispatcher metrics, which read ``bench.*`` spans that
    the served path has not, read nothing there."""
    from repro.runtime import telemetry
    from bench.tests.test_bench_harness import SERVE, drive, small_cell
    _with_a_chip(monkeypatch)
    cell = small_cell(SERVE)
    line, lines = drive(cell, harness.make_system(cell), traced=True)
    assert line["correct"], line["checks"]
    assert not telemetry.TRACER.enabled
    assert line["attempted"] >= 9 * 4       # whole blocks of a second
    metrics = line["metrics"]
    assert "frontend_pct" not in metrics
    assert "dispatch_host_pct" not in metrics
    assert 0 <= metrics["serve_queue_pct"]["value"] < 100
    assert 0 < metrics["device_idle_pct"]["value"] < 100
    for name in bspans.SHARES:
        assert 0 < metrics[name]["value"] < 100, name
    assert set(metrics) <= set(cell.per_layer)
    assert any(n.startswith(bspans.PREFIX) for n, _ in
               line["breakdown"]["idle_gaps_by_program_span"])
    assert "writer_late_s" in lines[0]["client"]
