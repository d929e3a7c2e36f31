"""The harness off the chip: it refuses to run without a TPU or with the
wrong chip count, and with the chip check stepped over it drives a whole
run on the CPU at a small size -- correct against the reference, not
correct under the control or with the timed path broken underneath."""

import dataclasses
import functools
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import control, harness, traffic
from bench import spans as bspans
from bench import trace as btrace
from repro.kernels import ops as kops
from repro.launch import serve as srv

ROWS = 2048
UFUNC_CELLS = ["fp32-fig9-64Mi", "int32-fig9-4Mi", "fp32-fig9-64Mi-x4"]
SERVE = "fp32-serve-open"
#: The serve mix at a size and rate the CPU holds: a block is 9 requests.
SMALL_SERVE = {"row_counts": {"64": 2, "256": 1}, "rate_per_s": 400.0}
#: The family of every committed configuration.
EW = harness.load_family("elementwise")


@dataclasses.dataclass
class FakeDevice:
    platform: str = "cpu"
    device_kind: str = "TPU v5 lite"
    id: int = 0

    def memory_stats(self):
        return None


def serve_cell() -> harness.Cell:
    """The served cell, which BENCHMARK.json does not name yet (PERF.md
    §7): the ``fig9-fp32`` configuration under the ``serve-open`` mix,
    reporting every metric that reads the served path."""
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    config = harness._read_json(os.path.join(harness.BENCH, "configs",
                                             "fig9-fp32.json"))
    mix = harness._read_json(os.path.join(harness.BENCH, "traffic",
                                          "serve-open.json"))
    per_layer = [m["name"] for m in spec["per_layer"]] + ["serve_queue_pct"]
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    return harness.Cell(name=SERVE, chips=1, config=config, mix=mix,
                        family=EW,
                        end_to_end=[m["name"] for m in spec["end_to_end"]],
                        per_layer=per_layer,
                        units=dict(units, serve_queue_pct="%"))


def small_cell(name: str, **mix) -> harness.Cell:
    cell = serve_cell() if name == SERVE else harness.load_cell(name)
    cell.config = dict(cell.config, rows=ROWS)
    if name == SERVE:
        mix = dict(SMALL_SERVE, **mix)
    cell.mix = dict(cell.mix, **mix)
    return cell


def drive(cell, system, traced=False, seed=2**31 + 7):
    lines = []
    line = harness.run_cell(cell, seed, 0.05, traced, jax=jax,
                            devices=[FakeDevice()], system=system,
                            t_process=0.0, emit=lines.append)
    return line, lines


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fp32-fig9-64Mi",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_refuses_to_run_without_the_program(tmp_path):
    """A checkout of the benchmark alone, without the system under
    test, exits nonzero and prints no result."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "int32-fig9-4Mi",
         "--seed", str(2**40), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


class _FakeJax:
    def __init__(self, devices):
        self._devices = devices

    def devices(self):
        return self._devices


def test_refuses_the_wrong_chip_count():
    tpu = FakeDevice(platform="tpu")
    assert harness.require_chips(_FakeJax([tpu] * 4), 4)
    with pytest.raises(harness.BenchError, match="needs 1 chip"):
        harness.require_chips(_FakeJax([tpu] * 4), 1)
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.require_chips(_FakeJax([FakeDevice()]), 1)


def test_a_traced_run_adds_its_compiler_flags(monkeypatch):
    """A traced run keeps the TPU compiler flags the machine sets and adds
    its own; an untraced run leaves them as they are."""
    monkeypatch.setattr(harness, "require_chips", lambda jax, chips: [])
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--machine_flag=1")
    for name in ("JAX_COMPILATION_CACHE_DIR",
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "TPU_LOG_DIR"):
        # set first, so that what start_jax sets is undone after the test
        # and no later test of this process inherits it
        monkeypatch.setenv(name, "")
        monkeypatch.delenv(name)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_compilation_cache_max_size")}
    try:
        harness.start_jax(1)
        assert os.environ["LIBTPU_INIT_ARGS"] == "--machine_flag=1"
        harness.start_jax(1, traced=True)
        assert os.environ["LIBTPU_INIT_ARGS"].split() == [
            "--machine_flag=1", *harness.TRACED_FLAGS]
        assert jax.config.jax_compilation_cache_max_size == -1
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.device_peaks("TPU v99")


def test_every_cell_names_files_that_exist():
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_metric(m))


def test_a_metric_is_read_only_in_the_cells_it_lists():
    """Every cell reads every metric; a ``workloads`` list in
    ``BENCHMARK.json`` only names the cells where the reader finds
    something, and each reader of the harness's ``bench.*`` spans or of
    one program span finds nothing, not a false 0, in a run that has none
    of them."""
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    names = [w["name"] for w in spec["workloads"]]
    for n in names:
        cell = harness.load_cell(n)
        assert cell.end_to_end == ["rows_per_s", "call_p95_s", "setup_s"]
        assert cell.per_layer == [m["name"] for m in spec["per_layer"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", names)) <= set(names), m["name"]
    served = btrace.Reduced(window_s=1.0, chips=1, busy_s=0.5, span_s={},
                            dispatch_host_s=0.0, executor_s=0.5,
                            kernels={}, device_ops={}, idle_by_span={})
    readings = harness.Readings(
        setup_s=1.0, calls=[], window_s=1.0, work={}, peaks={},
        trace=served, spans=bspans.ProgramSpans(
            window_s=1.0, intervals={"pim.dispatch.pack": [(0, 10)]},
            idle_by_span={}))
    for name in ("frontend_pct", "dispatch_host_pct", "prepare_check_pct"):
        assert harness.load_metric(name)(readings) is None, name


@pytest.mark.parametrize("name", ["fp32-fig9-64Mi", "int32-fig9-4Mi",
                                  SERVE])
def test_a_run_on_the_cpu_is_correct(name):
    cell = small_cell(name)
    line, lines = drive(cell, harness.make_system(cell))
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"rows_per_s", "call_p95_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert all(c == {"value": 0, "limit": 0}
               for c in line["checks"].values())
    window = lines[0]["window"]
    if name != SERVE:
        assert window["compiles"] == 0
        assert set(lines[0]) == {"window", "counters"}
        return
    # the served path's compiles are timing's to decide: reported, as is
    # how late the writer ran and how many blocks warmed it up
    assert line["attempted"] % 9 == 0
    assert "mismatch_rows.fp_div" in line["checks"]
    assert line["checks"]["extra_responses"] == {"value": 0, "limit": 0}
    assert window["compiles"] >= 0 and window["compile_s"] >= 0
    client = lines[0]["client"]
    assert 1 <= client["warmup_blocks"] <= harness.WARMUP_BLOCKS
    assert 0 <= client["writer_late_s"]["p95"] <= \
        client["writer_late_s"]["max"]
    assert set(window["call_s_by_rows"]) == {64, 256}


def test_seed_fixes_operands_and_order():
    cell = small_cell("int32-fig9-4Mi")
    a = traffic.make(cell.mix, cell.config, 3 * 2**31, EW)
    b = traffic.make(cell.mix, cell.config, 3 * 2**31, EW)
    assert a.block == b.block
    assert sorted(s.op for s in a.block) == sorted(cell.config["ops"])
    for (xa, ya), (xb, yb) in zip(a.pool, b.pool):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert ya.min() >= 1
    # the pool's pairs differ, and calls in a row take different pairs
    assert len(a.pool) == 2 and not np.array_equal(a.pool[0][0],
                                                   a.pool[1][0])
    assert [a.pair(i) for i in range(4)] == [0, 1, 0, 1]
    assert a.path == "ufunc"
    # the serve mix as committed: 63 requests of 64,512 rows a block
    serve = serve_cell()
    a = traffic.make(serve.mix, serve.config, 3 * 2**31, EW)
    b = traffic.make(serve.mix, serve.config, 3 * 2**31, EW)
    assert a.block == b.block and a.path == "serve" and a.open_loop
    for (xa, ya), (xb, yb) in zip(a.pool, b.pool):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert xa.dtype == np.float32 and len(xa) == 16384
    assert len(a.block) == 63 and sum(s.rows for s in a.block) == 64512
    for op in serve.config["ops"]:
        sizes = sorted(s.rows for s in a.block if s.op == op)
        assert sizes == [64] * 16 + [1024] * 4 + [16384]


def test_every_seed_sends_the_same_work_in_its_own_order():
    mix = {"arrivals": "poisson", "rate_per_s": 50.0, "order": "shuffled",
           "op_counts": {"add": 3, "mul": 2, "div": 1},
           "rows": [64, 1024], "pool": 3, "row_shards": 1}
    config = harness.load_cell("int32-fig9-4Mi").config
    blocks = [traffic.make(mix, config, seed, EW).block
              for seed in (1, 2**40)]
    assert blocks[0] != blocks[1]
    for key in (lambda s: (s.op, s.rows), lambda s: s.gap_s):
        assert sorted(map(key, blocks[0])) == sorted(map(key, blocks[1]))
    assert len(blocks[0]) == 12
    assert sum(s.gap_s for s in blocks[0]) == pytest.approx(12 / 50.0,
                                                            rel=0.2)
    for bad in ({"arrivals": "bursty"}, {"order": "sorted"},
                {"op_counts": {"fp_add": 1}},
                {"arrivals": "poisson", "rate_per_s": 0},
                {"path": "rpc"}, {"row_counts": {"64": 2}},
                {"rows": None, "row_counts": {"64": 0}}):
        with pytest.raises(ValueError):
            traffic.make({k: v for k, v in dict(mix, **bad).items()
                          if v is not None}, config, 1, EW)
    # sizes by count: each op call of op_counts at each size, that often
    counted = dict(mix, row_counts={"64": 3, "1024": 1})
    del counted["rows"]
    blocks = [traffic.make(counted, config, seed, EW).block
              for seed in (1, 2**40)]
    assert blocks[0] != blocks[1] and len(blocks[0]) == 24
    for key in (lambda s: (s.op, s.rows), lambda s: s.gap_s):
        assert sorted(map(key, blocks[0])) == sorted(map(key, blocks[1]))
    assert sorted(s.rows for s in blocks[0] if s.op == "mul") == \
        [64] * 6 + [1024] * 2


def test_an_open_loop_counts_the_wait_from_arrival():
    cell = small_cell("int32-fig9-4Mi", arrivals="poisson",
                      rate_per_s=1000.0, op_counts={"add": 2, "mul": 1},
                      rows=[256, ROWS])
    line, lines = drive(cell, harness.Ufuncs(cell.config, EW))
    assert line["correct"], line["checks"]
    assert line["attempted"] % 6 == 0
    assert lines[0]["window"]["compiles"] == 0
    assert set(line["checks"]) == {"mismatch_rows.add", "mismatch_rows.mul"}


@pytest.mark.parametrize("dtype,keep", [("float16", 2), ("float32", 7),
                                        ("float64", 23)])
def test_fp_operands_and_control_for_any_ieee_format(dtype, keep):
    rng = traffic.rng_for(2**33 + 1)
    x = traffic.fp_operands(rng, dtype, 4096, -4, 5)
    y = traffic.fp_operands(rng, dtype, 4096, -4, 5)
    assert x.dtype == np.dtype(dtype)
    assert np.all(np.isfinite(x)) and np.all(np.abs(x) >= 2.0**-4)
    assert np.all(np.abs(x) < 2.0**6)
    drop = np.finfo(dtype).nmant - keep
    for op in ("fp_add", "fp_mul", "fp_div"):
        want = EW.reference(op, x, y)
        low = EW.control(op, x, y)
        assert low.dtype == x.dtype             # as the ufunc returns it
        assert np.all(low.view(want.dtype).astype(np.uint64)
                      % (1 << drop) == 0)
        assert EW.mismatched(op, low, want, len(x)) > 3000
    with pytest.raises(ValueError, match="normal range"):
        traffic.fp_operands(rng, "float16", 4, -20, 5)


def test_the_configuration_sets_the_algorithm():
    config = harness.load_cell("fp32-fig9-64Mi").config
    assert harness.ufunc_options(config) == {"parallel": False}
    assert harness.ufunc_options(dict(config, algorithm="bit-parallel")) \
        == {"parallel": True}
    with pytest.raises(harness.BenchError, match="unknown algorithm"):
        harness.ufunc_options(dict(config, algorithm="bit-sliced"))
    serial = EW.work("fp_add", config)
    parallel = EW.work("fp_add", dict(config, algorithm="bit-parallel"))
    assert serial.nor_gates != parallel.nor_gates
    assert (serial.in_bits, serial.out_bits) == \
        (parallel.in_bits, parallel.out_bits)


def test_a_bit_parallel_configuration_runs_bit_parallel():
    cell = small_cell("fp32-fig9-64Mi")
    cell.config = dict(cell.config, algorithm="bit-parallel", rows=256)
    system = harness.Ufuncs(cell.config, EW)
    tr = traffic.make(cell.mix, cell.config, 9, EW)
    from repro.core.pim_numerics import program_for
    x, y = tr.pool[0]
    prep = system.pim.prepare("fp_mul", x, y, **system.kw)
    assert prep.program is program_for("fp-parallel", "mul", "fp32")
    line, _ = drive(cell, system)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("name", ["fp32-fig9-64Mi", "int32-fig9-4Mi",
                                  SERVE])
def test_the_control_is_not_correct(name):
    cell = small_cell(name)
    line, _ = drive(cell, control.control_system(harness, cell))
    assert not line["correct"]
    assert max(c["value"] for c in line["checks"].values()) > 0


def _answer_altered(monkeypatch):
    orig = kops.run_program_streaming

    def altered(*a, **kw):
        outs = orig(*a, **kw)
        name = sorted(outs)[0]
        outs[name] = outs[name].copy()
        outs[name][len(outs[name]) // 3] ^= 1
        return outs
    monkeypatch.setattr(kops, "run_program_streaming", altered)


def _rows_left_out(share):
    def patch(monkeypatch):
        orig = kops.run_program_streaming

        def part(program, inputs, n_rows, plan=None, **kw):
            keep = n_rows - int(n_rows * share)
            outs = orig(program, {n: v[:keep] for n, v in inputs.items()},
                        keep, plan, **kw)
            return {n: np.concatenate([v, np.zeros(n_rows - keep, v.dtype)])
                    for n, v in outs.items()}
        monkeypatch.setattr(kops, "run_program_streaming", part)
    return patch


def _state_unchanged(monkeypatch):
    for name in ("pim_exec_ref_slots_fused", "pim_exec_ref_slots_io"):
        orig = getattr(kops, name)

        def no_levels(in_rows, in_idx, la, lb, lo, out_idx, _orig=orig,
                      **static):
            return _orig(in_rows, in_idx, la[:0], lb[:0], lo[:0], out_idx,
                         **static)
        monkeypatch.setattr(kops, name, no_levels)


@pytest.mark.parametrize("name", ["fp32-fig9-64Mi", "int32-fig9-4Mi"])
@pytest.mark.parametrize("fault", [_answer_altered, _rows_left_out(0.5),
                                   _rows_left_out(0.25), _state_unchanged],
                         ids=["answer_altered", "half_the_rows_left_out",
                              "one_shard_of_four_left_out",
                              "level_loop_skipped"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    cell = small_cell(name)
    system = harness.Ufuncs(cell.config, EW)
    harness.warm_up(system, traffic.make(cell.mix, cell.config, 1, EW))
    fault(monkeypatch)                          # compiled before breaking
    line, _ = drive(cell, system)
    assert not line["correct"]
    assert line["failed"] > 0


def _reference_server(stall_s: float = 0.0):
    """A server of ``--pim-serve``'s wire format that answers each request,
    in order, with the plain reference, after ``stall_s`` seconds in which
    it reads nothing."""
    def serve(inp, outp):
        time.sleep(stall_s)
        for line in inp:
            op, (x, y) = EW.WIRE.parse(json.loads(line))
            out = EW.as_result(op, EW.reference(op, x, y), x.dtype)
            print(json.dumps(dict(EW.WIRE.response(op, (x, y), out),
                                  queue_us=0.0)), file=outp, flush=True)
    return serve


def test_the_served_warm_up_sends_blocks_until_one_compiles_nothing(
        monkeypatch):
    """The served warm-up sends whole blocks through the server at the
    mix's rate and stops after the first block that adds no
    ``pim.jit.compiles``, or after :data:`harness.WARMUP_BLOCKS`."""
    cell = small_cell(SERVE)
    tr = traffic.make(cell.mix, cell.config, 2**31 + 11, EW)
    for compiling, blocks in ((2, 3), (99, harness.WARMUP_BLOCKS)):
        system = harness.Served(cell.config, EW, serve=_reference_server())
        sent = []

        def counters(system=system, compiling=compiling):
            n = system.sent // len(tr.block)
            sent.append(n)
            return {"pim.jit.compiles": min(n, compiling)}
        monkeypatch.setattr(system, "counters", counters)
        try:
            system.warm_up(tr)
        finally:
            system.close()
        assert system.warmup_blocks == blocks
        assert system.sent == blocks * len(tr.block)


def test_a_stalled_server_counts_the_wait_from_arrival():
    """A server that reads nothing for half a second: the requests' pipe
    fills, the writer runs late, and every latency still runs from the
    request's arrival on the schedule, not from its late write."""
    cell = small_cell(SERVE, row_counts={"64": 2, "16384": 1},
                      rate_per_s=200.0)
    config = dict(cell.config, rows=16384)
    tr = traffic.make(cell.mix, config, 2**33 + 3, EW)
    stall = 0.5
    system = harness.Served(config, EW, serve=_reference_server(stall))
    system.start(tr)
    t0 = time.perf_counter()
    calls, results, window_s = system.send(tr, 2)
    assert system.close() == 0
    arrival = np.cumsum([s.gap_s for s in tr.block * 2])
    assert arrival[-1] < stall / 2           # all due within the stall
    late = system.report["writer_late_s"]
    assert late["max"] > stall / 4           # the full pipe held it back
    for c, due in zip(calls, arrival):
        assert c.error is None
        assert c.seconds >= stall - due - 1e-3
    assert max(c.seconds for c in calls) >= late["max"]
    assert window_s >= stall and time.perf_counter() - t0 >= window_s
    checks = harness.check(calls, results, tr)
    assert all(c["value"] == 0 for c in checks.values())


class _Lines:
    """The server's response stream, each line handed to ``edit`` with its
    index in the stream; what ``edit`` returns is written in its place."""

    def __init__(self, outp, edit):
        self.outp, self.edit, self.k, self.part = outp, edit, 0, ""

    def write(self, text):
        self.part += text
        while "\n" in self.part:
            line, self.part = self.part.split("\n", 1)
            for out in self.edit(self.k, line):
                self.outp.write(out + "\n")
            self.k += 1
        return len(text)

    def flush(self):
        self.outp.flush()


def _lines_edited(edit):
    """A fault in the wire: the server's response lines through ``edit``.
    A block of the small serve mix is 9 requests, so line 4 of every 9 is
    inside a block whatever phase it falls in."""
    def patch(monkeypatch):
        serve = functools.partial(srv.serve_pim_batched,
                                  **harness.SERVE_OPTIONS)
        # long enough for the server to end under a loaded test host,
        # short enough that a dropped response costs seconds, not minutes
        monkeypatch.setattr(harness, "ANSWER_GRACE_S", 5.0)
        return lambda inp, outp: serve(inp, _Lines(outp, edit))
    return patch


def _dropped(k, line):
    return [] if k % 9 == 4 else [line]


def _swapped():
    held = []

    def edit(k, line):
        if k % 9 == 4:
            held.append(line)
            return []
        return [line, held.pop()] if k % 9 == 5 else [line]
    return edit


def _error_body(k, line):
    if k % 9 != 4:
        return [line]
    return [json.dumps({"error": {"code": "internal", "message": "planted",
                                  "retriable": True}})]


def _group_answer_altered(monkeypatch):
    orig = kops.run_program_groups

    def altered(groups):
        outs = orig(groups)
        for o in outs:
            name = sorted(o)[0]
            o[name] = o[name].copy()
            o[name][len(o[name]) // 3] ^= 1
        return outs
    monkeypatch.setattr(kops, "run_program_groups", altered)


def _group_half_left_out(monkeypatch):
    orig = kops.run_program_groups

    def half(groups):
        groups = list(groups)
        kept = [dict(g, n_rows=g["n_rows"] - g["n_rows"] // 2,
                     inputs={n: v[:g["n_rows"] - g["n_rows"] // 2]
                             for n, v in g["inputs"].items()})
                for g in groups]
        return [{n: np.concatenate([v, np.zeros(g["n_rows"] - len(v),
                                                v.dtype)])
                 for n, v in o.items()}
                for g, o in zip(groups, orig(kept))]
    monkeypatch.setattr(kops, "run_program_groups", half)


@pytest.mark.parametrize("fault", [
    _group_answer_altered, _lines_edited(_dropped),
    _lines_edited(_swapped()), _lines_edited(_error_body),
    _group_half_left_out, _state_unchanged],
    ids=["answer_altered", "response_dropped", "responses_swapped",
         "error_body", "half_the_rows_left_out", "level_loop_skipped"])
def test_a_broken_served_path_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(harness, "WARMUP_BLOCKS", 2)
    cell = small_cell(SERVE)
    serve = fault(monkeypatch)
    line, _ = drive(cell, harness.Served(cell.config, EW, serve=serve))
    assert not line["correct"]
    assert line["failed"] > 0


def test_reference_and_control_differ_as_stated():
    rng = traffic.rng_for(5)
    x = traffic.fp_operands(rng, "float32", 4096, -4, 5)
    y = traffic.fp_operands(rng, "float32", 4096, -4, 5)
    for op in ("fp_add", "fp_mul", "fp_div"):
        want = EW.reference(op, x, y)
        low = EW.control(op, x, y).view(np.uint32)
        assert np.all(low & 0xFFFF == 0)
        assert EW.mismatched(op, want, low, len(x)) > 4000
    xi = traffic.int_operands(rng, "uint32", 4096, 0)
    yi = traffic.int_operands(rng, "uint32", 4096, 1)
    assert EW.mismatched(
        "add", EW.reference("add", xi, yi),
        EW.control("add", xi, yi), len(xi)) > 1000
    q, r = EW.reference("div", xi, yi)
    assert np.array_equal(q * yi + r, xi.astype(np.uint64))


_FOUR_CHIPS = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import jax
from bench import harness
from bench.tests.test_bench_harness import FakeDevice
cell = harness.load_cell("fp32-fig9-64Mi")
cell.config = dict(cell.config, rows=8192)
with open(os.path.join(harness.BENCH, "traffic", "closed-rotation-x4.json")) as f:
    cell.mix = json.load(f)
line = harness.run_cell(cell, 2**32 + 5, 0.05, False, jax=jax,
                        devices=[FakeDevice(id=i) for i in range(4)],
                        system=harness.Ufuncs(cell.config, cell.family),
                        t_process=0.0,
                        emit=lambda d: None)
print(json.dumps(line))
"""


def test_the_four_chip_mix_shards_and_is_correct_on_four_cpu_devices():
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIPS.format(root=harness.ROOT)],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
