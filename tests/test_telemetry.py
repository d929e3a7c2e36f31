"""Unified telemetry (DESIGN.md §15): log-bucketed histogram math,
registry thread-safety and drain semantics, CounterGroup Counter-compat,
trace spans / Chrome-trace export, the analytical cost model, Prometheus
rendering, and the structural overhead pin for the tracked kernel row."""

import json
import math
import threading

import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.runtime import telemetry
from repro.runtime.telemetry import (CounterGroup, Histogram,
                                     MetricsRegistry, PimCostModel, Tracer,
                                     render_prometheus)


@pytest.fixture(autouse=True)
def _registry_leak_check():
    """Mirror tests/test_faults.py: the global registry is shared state,
    so every test starts from drained model/exec/cache counters and must
    not leave health/media counters behind for its neighbours."""
    telemetry.drain_model_counters()
    telemetry.REGISTRY.drain("pim.cache.")
    kops.drain_health()
    yield
    telemetry.drain_model_counters()
    telemetry.REGISTRY.drain("pim.cache.")
    leaked = kops.drain_health()
    assert not leaked, f"test leaked undrained HEALTH counters: {leaked}"


# ------------------------------------------------------------- histograms

def test_histogram_bucket_edges_exact():
    """Powers of 2**(1/4) are bucket edges: observing exactly [1,2,4,8]
    makes every quantile land on an edge, so p50 is exactly 2.0 (no
    interpolation error at edges)."""
    h = Histogram()
    for v in (1.0, 2.0, 4.0, 8.0):
        h.observe(v)
    assert h.percentile(0.50) == pytest.approx(2.0)
    assert h.percentile(0.0) >= 1.0
    assert h.percentile(1.0) == pytest.approx(8.0)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 1.0 and s["max"] == 8.0
    assert s["sum"] == pytest.approx(15.0)


def test_histogram_percentile_accuracy_and_monotonicity():
    """Bucket width bounds the relative error: estimates stay within the
    ~19%-wide bucket of the true quantile, and quantiles never invert."""
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=3.0, sigma=1.5, size=5000)
    h = Histogram()
    for v in vals:
        h.observe(float(v))
    prev = 0.0
    for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99):
        true = float(np.quantile(vals, q))
        est = h.percentile(q)
        assert est == pytest.approx(true, rel=0.20)
        assert est >= prev        # monotone in q
        prev = est
    assert h.percentile(1.0) == pytest.approx(float(vals.max()))


def test_histogram_single_value_and_zeros():
    h = Histogram()
    h.observe(37.0)
    for q in (0.0, 0.5, 0.99, 1.0):   # clamped to the [min,max] envelope
        assert h.percentile(q) == pytest.approx(37.0)
    hz = Histogram()
    hz.observe(0.0)
    hz.observe(-1.0)
    assert hz.zeros == 2 and hz.count == 2
    assert hz.percentile(0.5) == 0.0
    empty = Histogram().summary()
    assert empty == {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                     "mean": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    assert math.isnan(Histogram().percentile(0.5))


# ------------------------------------------------------------- registry

def test_registry_drain_resets_to_zero():
    reg = MetricsRegistry()
    reg.inc("a.x", 3)
    reg.inc("a.y", 2)
    reg.inc("b.z")
    assert reg.drain("a.") == {"a.x": 3, "a.y": 2}
    assert reg.drain("a.") == {}                  # drained clean
    assert reg.counter("a.x") == 0
    assert reg.drain() == {"b.z": 1}
    reg.observe("h", 5.0)
    assert reg.drain_histograms()["h"]["count"] == 1
    assert reg.summary("h") is None               # histogram drained too


def test_registry_threaded_increments_exact():
    """8 threads x 10k atomic adds through every mutation surface: the
    single registry lock must lose nothing (the historical ``HEALTH``
    Counter was unguarded; this is the regression test for its fix)."""
    reg = MetricsRegistry()
    grp = reg.group("pim.t")
    per, nthreads = 10_000, 8

    def worker():
        for _ in range(per):
            grp.add("k")
            reg.inc("raw")
            reg.observe("h", 1.0)

    ts = [threading.Thread(target=worker) for _ in range(nthreads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert grp["k"] == per * nthreads
    assert reg.counter("raw") == per * nthreads
    assert reg.summary("h")["count"] == per * nthreads


def test_counter_group_counter_compat():
    """The Counter surface the HEALTH/MEDIA call sites ride on."""
    reg = MetricsRegistry()
    g = reg.group("pim.g")
    assert not g and len(g) == 0
    g.add("hits", 2)
    g["gauge"] = 7                         # absolute set (spans_still_bad)
    g.add("hits")
    assert g["hits"] == 3 and g.get("none") == 0 and "hits" in g
    assert sorted(g.keys()) == ["gauge", "hits"] and bool(g)
    assert dict(g.items())["gauge"] == 7
    assert g.drain() == {"hits": 3, "gauge": 7}
    assert not g and g["hits"] == 0        # drain reset the view
    g.add("x")
    g.clear()
    assert len(g) == 0
    assert isinstance(g.registry, MetricsRegistry)


def test_drain_health_shim_still_counter_shaped():
    """ops.HEALTH is now a registry view; its historical drain contract
    (plain non-zero int dict, reset on read) must survive unchanged."""
    kops.HEALTH.add("retries", 2)
    kops.HEALTH.add("faults_detected")
    got = kops.drain_health()
    assert got == {"retries": 2, "faults_detected": 1}
    assert kops.drain_health() == {}


# ------------------------------------------------------------- tracer

def test_tracer_disabled_is_null_and_enabled_nests():
    tr = Tracer()
    assert tr.span("x") is telemetry._NULL_SPAN      # shared, no alloc
    tr.event("x", 0.0, 1.0)
    tr.instant("y")
    assert tr.drain() == []                          # disabled: recorded 0
    tr.enabled = True
    with tr.span("outer", cat="test", rows=4):
        with tr.span("inner", cat="test"):
            pass
    tr.instant("mark", cat="test")
    evs = tr.drain()
    assert [e["name"] for e in evs] == ["inner", "outer", "mark"]
    outer = evs[1]
    assert outer["ph"] == "X" and outer["pid"] == 1 and "tid" in outer
    assert outer["args"] == {"rows": 4}
    assert outer["dur"] >= evs[0]["dur"]             # inner nests inside
    assert evs[2]["dur"] == 0.0                      # instant
    assert tr.drain() == []                          # drained clean


def test_tracer_chrome_trace_file(tmp_path):
    tr = Tracer()
    tr.enabled = True
    with tr.span("stage", cat="pim.serve"):
        pass
    p = tmp_path / "trace.json"
    assert tr.write_chrome_trace(str(p)) == 1
    doc = json.loads(p.read_text())
    assert doc["displayTimeUnit"] == "ms"
    (ev,) = doc["traceEvents"]
    assert ev["name"] == "stage" and ev["cat"] == "pim.serve"
    assert ev["ts"] >= 0 and ev["dur"] >= 0


def test_tracer_ring_is_bounded():
    tr = Tracer(capacity=8)
    tr.enabled = True
    for i in range(20):
        tr.instant(f"e{i}")
    evs = tr.drain()
    assert len(evs) == 8 and evs[0]["name"] == "e12"  # oldest dropped


# ------------------------------------------------------------- cost model

def test_cost_model_schedule_and_program():
    from repro.core.pim_numerics import program_for
    prog = program_for("int-serial", "add", 8)
    sched = kops.program_schedule(prog)
    m = telemetry.COST_MODEL.schedule_cost(sched)
    assert m.gates == int(sched.n_gates) + int(sched.copy_gates)
    assert m.cycles == m.gates + m.init_cycles
    assert m.levels == int(sched.n_levels)
    assert m.io_bits == sum(len(c) for c in sched.ports.values())
    assert m.latency_us == pytest.approx(
        m.cycles * telemetry.PIM_DEFAULT.cycle_ns * 1e-3)
    assert m.energy_pj(10) == pytest.approx(10 * m.energy_pj_per_row)
    # gate term alone bounds energy from below
    assert m.energy_pj_per_row > m.cycles * telemetry.ENERGY_PJ["nor"]
    ms = telemetry.COST_MODEL.program_cost(prog.cost())
    assert ms.gates == prog.cost().nor_gates
    # the serial order pays every INIT; the levelized schedule folds them
    assert ms.cycles >= m.init_cycles


def test_record_dispatch_fills_model_counters():
    from repro.core.pim_numerics import program_for
    prog = program_for("int-serial", "add", 8)
    rng = np.random.default_rng(0)
    ins = {"x": rng.integers(0, 256, 16).astype(np.uint64),
           "y": rng.integers(0, 256, 16).astype(np.uint64)}
    telemetry.drain_model_counters()
    kops.run_program(prog, ins, 16, backend="ref")
    c = telemetry.drain_model_counters()
    assert c["pim.exec.dispatches"] == 1 and c["pim.exec.rows"] == 16
    m = telemetry.COST_MODEL.schedule_cost(kops.program_schedule(prog))
    assert c["pim.model.cycles"] == m.cycles
    assert c["pim.model.energy_pj"] == pytest.approx(m.energy_pj(16))
    # the numpy oracle records through the serial model, no cache entry
    n_entries = len(kops._compiled)
    kops.run_program(prog, ins, 16, backend="numpy")
    c2 = telemetry.drain_model_counters()
    assert c2["pim.exec.dispatches"] == 1
    assert c2["pim.model.cycles"] == telemetry.COST_MODEL.program_cost(
        prog.cost()).cycles
    assert len(kops._compiled) == n_entries


class _Annotation:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that records each
    annotation made."""
    made: list = []

    def __init__(self, name, **args):
        self.made.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def test_dispatch_overhead_is_structural(monkeypatch):
    """The <2% overhead budget on kernel/fp16_add_8k_rows, pinned
    structurally: one dispatch performs exactly one registry lock
    acquisition (one add_many) and zero tracer work when disabled --
    independent of row count and schedule size, and no profiler
    annotation is made.  Every shape is compiled before the count: a
    compile is counted on its own (``pim.jit.*``).  Enabled, each span
    reaches both the ring and the profiler."""
    import jax
    from repro.core.pim_numerics import program_for
    prog = program_for("int-serial", "add", 8)
    rng = np.random.default_rng(1)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.made = []
    shapes = [(n, None) for n in (8, 64)] + [(96, 32)]    # 3 chunks

    def run(n, chunk_rows, ins):
        if chunk_rows is None:
            return kops.run_program(prog, ins, n, backend="ref")
        return kops.run_program_streaming(prog, ins, n, backend="ref",
                                          chunk_rows=chunk_rows)

    cases = [(n, c, {"x": rng.integers(0, 256, n).astype(np.uint64),
                     "y": rng.integers(0, 256, n).astype(np.uint64)})
             for n, c in shapes]
    for case in cases:
        run(*case)                          # compiles, outside the count
    calls = {"add_many": 0, "observe": 0}
    orig_add_many = telemetry.REGISTRY.add_many
    orig_observe = telemetry.REGISTRY.observe

    def counting_add_many(d):
        calls["add_many"] += 1
        orig_add_many(d)

    def counting_observe(n, v):
        calls["observe"] += 1
        orig_observe(n, v)

    telemetry.REGISTRY.add_many = counting_add_many
    telemetry.REGISTRY.observe = counting_observe
    try:
        for n, chunk_rows, ins in cases:
            before = dict(calls)
            run(n, chunk_rows, ins)
            dispatches = 1 if chunk_rows is None else -(-n // chunk_rows)
            assert calls["add_many"] - before["add_many"] == dispatches
            assert calls["observe"] == before["observe"]
    finally:
        telemetry.REGISTRY.add_many = orig_add_many
        telemetry.REGISTRY.observe = orig_observe
    assert not telemetry.TRACER.enabled    # default: spans are one attr read
    assert _Annotation.made == []
    assert telemetry.TRACER.drain() == []

    n, chunk_rows, ins = cases[-1]
    telemetry.TRACER.enabled = True
    try:
        run(n, chunk_rows, ins)
    finally:
        telemetry.TRACER.enabled = False
    ring = [(e["name"], e.get("args", {})) for e in telemetry.TRACER.drain()]
    assert sorted(ring, key=repr) == sorted(_Annotation.made, key=repr)
    names = [name for name, _ in ring]
    for step in ("pack", "h2d", "launch", "wait", "d2h", "unpack"):
        assert names.count(f"pim.dispatch.{step}") == 3, step
    assert names.count("pim.dispatch.concat") == 1
    assert {a["chunk"] for name, a in ring
            if name == "pim.dispatch.pack"} == {0, 1, 2}


def test_compile_counter_counts_new_shapes_only():
    """``pim.jit.compiles`` rises when a shape compiles and not when the
    same shape runs again."""
    import time
    import jax
    k = time.time_ns() % 1_000_003         # a program no cache holds yet
    f = jax.jit(lambda v: v * k + 3)

    def compiles():
        return telemetry.REGISTRY.counter("pim.jit.compiles")

    n0, s0 = compiles(), telemetry.REGISTRY.counter("pim.jit.compile_s")
    f(np.arange(37, dtype=np.int32)).block_until_ready()
    n1 = compiles()
    assert n1 > n0
    assert telemetry.REGISTRY.counter("pim.jit.compile_s") > s0
    f(np.arange(37, dtype=np.int32)).block_until_ready()
    assert compiles() == n1


def test_compiled_cache_hit_miss_counters():
    from repro.core.pim_numerics import program_for
    prog = program_for("int-serial", "add", 9)
    rng = np.random.default_rng(2)
    ins = {"x": rng.integers(0, 512, 8).astype(np.uint64),
           "y": rng.integers(0, 512, 8).astype(np.uint64)}
    kops._compiled.pop(kops.cache_key(prog, kops.make_plan(backend="ref")),
                       None)
    telemetry.REGISTRY.drain("pim.cache.")
    kops.run_program(prog, ins, 8, backend="ref")
    kops.run_program(prog, ins, 8, backend="ref")
    c = telemetry.REGISTRY.drain("pim.cache.")
    assert c["pim.cache.misses"] == 1
    assert c.get("pim.cache.hits", 0) >= 1


# ------------------------------------------------------------- prometheus

def test_render_prometheus():
    reg = MetricsRegistry()
    reg.inc("pim.serve.requests", 5)
    reg.set_gauge("pim.serve.depth", 2.5)
    for v in (1.0, 2.0, 4.0, 8.0):
        reg.observe("pim.serve.queue_us", v)
    text = render_prometheus(reg)
    assert "# TYPE pim_serve_requests counter\npim_serve_requests 5" in text
    assert "# TYPE pim_serve_depth gauge\npim_serve_depth 2.5" in text
    assert '# TYPE pim_serve_queue_us summary' in text
    assert 'pim_serve_queue_us{quantile="0.5"} 2' in text
    assert "pim_serve_queue_us_count 4" in text
    assert "pim_serve_queue_us_sum 15" in text
    assert text.endswith("\n")
    # multiple registries concatenate
    reg2 = MetricsRegistry()
    reg2.inc("other", 1)
    both = render_prometheus(reg, reg2)
    assert "pim_serve_requests 5" in both and "other 1" in both


def test_stats_is_registry_backed():
    """Serving Stats route through a per-runtime registry: attribute
    reads/writes, atomic add and as_dict stay coherent."""
    from repro.runtime.pim_batch import Stats
    st = Stats()
    assert st.requests == 0 and st.exec_s == 0.0
    st.add("requests", 3)
    st.rows = 128
    st.exec_s = 0.5
    assert st.requests == 3 and st.rows == 128
    assert st.rows_per_s() == pytest.approx(256.0)
    d = st.as_dict()
    assert d["requests"] == 3 and d["rows"] == 128
    assert isinstance(d["requests"], int)
    with pytest.raises(AttributeError):
        st.not_a_field
