"""The harness off the chip: it refuses to run without a TPU or with the
wrong chip count, and with the chip check stepped over it drives a whole
run on the CPU at a small size -- correct against the reference, not
correct under the control or with the timed path broken underneath."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import control, harness, reference, traffic, work
from repro.kernels import ops as kops

ROWS = 2048


@dataclasses.dataclass
class FakeDevice:
    platform: str = "cpu"
    device_kind: str = "TPU v5 lite"
    id: int = 0

    def memory_stats(self):
        return None


def small_cell(name: str, **mix) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, rows=ROWS)
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
        monkeypatch.delenv(name, raising=False)
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


@pytest.mark.parametrize("name", ["fp32-fig9-64Mi", "int32-fig9-4Mi"])
def test_a_run_on_the_cpu_is_correct(name):
    cell = small_cell(name)
    line, lines = drive(cell, harness.Ufuncs(cell.config))
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"rows_per_s", "call_p95_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert all(c == {"value": 0, "limit": 0}
               for c in line["checks"].values())
    assert lines[0]["window"]["compiles"] == 0


def test_seed_fixes_operands_and_order():
    cell = small_cell("int32-fig9-4Mi")
    a = traffic.make(cell.mix, cell.config, 3 * 2**31)
    b = traffic.make(cell.mix, cell.config, 3 * 2**31)
    assert a.block == b.block
    assert sorted(s.op for s in a.block) == sorted(cell.config["ops"])
    for (xa, ya), (xb, yb) in zip(a.pool, b.pool):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert ya.min() >= 1
    # the pool's pairs differ, and calls in a row take different pairs
    assert len(a.pool) == 2 and not np.array_equal(a.pool[0][0],
                                                   a.pool[1][0])
    assert [a.pair(i) for i in range(4)] == [0, 1, 0, 1]


def test_every_seed_sends_the_same_work_in_its_own_order():
    mix = {"arrivals": "poisson", "rate_per_s": 50.0, "order": "shuffled",
           "op_counts": {"add": 3, "mul": 2, "div": 1},
           "rows": [64, 1024], "pool": 3, "row_shards": 1}
    config = harness.load_cell("int32-fig9-4Mi").config
    blocks = [traffic.make(mix, config, seed).block for seed in (1, 2**40)]
    assert blocks[0] != blocks[1]
    for key in (lambda s: (s.op, s.rows), lambda s: s.gap_s):
        assert sorted(map(key, blocks[0])) == sorted(map(key, blocks[1]))
    assert len(blocks[0]) == 12
    assert sum(s.gap_s for s in blocks[0]) == pytest.approx(12 / 50.0,
                                                            rel=0.2)
    for bad in ({"arrivals": "bursty"}, {"order": "sorted"},
                {"op_counts": {"fp_add": 1}},
                {"arrivals": "poisson", "rate_per_s": 0}):
        with pytest.raises(ValueError):
            traffic.make(dict(mix, **bad), config, 1)


def test_an_open_loop_counts_the_wait_from_arrival():
    cell = small_cell("int32-fig9-4Mi", arrivals="poisson",
                      rate_per_s=1000.0, op_counts={"add": 2, "mul": 1},
                      rows=[256, ROWS])
    line, lines = drive(cell, harness.Ufuncs(cell.config))
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
        want = reference.reference(op, x, y)
        low = reference.control(op, x, y)
        assert np.all(low.astype(np.uint64) % (1 << drop) == 0)
        assert reference.mismatched_rows(op, want, low) > 3000
    with pytest.raises(ValueError, match="normal range"):
        traffic.fp_operands(rng, "float16", 4, -20, 5)


def test_the_configuration_sets_the_algorithm():
    config = harness.load_cell("fp32-fig9-64Mi").config
    assert harness.ufunc_options(config) == {"parallel": False}
    assert harness.ufunc_options(dict(config, algorithm="bit-parallel")) \
        == {"parallel": True}
    with pytest.raises(harness.BenchError, match="unknown algorithm"):
        harness.ufunc_options(dict(config, algorithm="bit-sliced"))
    serial = work.op_work("fp_add", "float32", "bit-serial")
    parallel = work.op_work("fp_add", "float32", "bit-parallel")
    assert serial.nor_gates != parallel.nor_gates
    assert (serial.in_bits, serial.out_bits) == \
        (parallel.in_bits, parallel.out_bits)


def test_a_bit_parallel_configuration_runs_bit_parallel():
    cell = small_cell("fp32-fig9-64Mi")
    cell.config = dict(cell.config, algorithm="bit-parallel", rows=256)
    system = harness.Ufuncs(cell.config)
    tr = traffic.make(cell.mix, cell.config, 9)
    from repro.core.pim_numerics import program_for
    x, y = tr.pool[0]
    prep = system.pim.prepare("fp_mul", x, y, **system.kw)
    assert prep.program is program_for("fp-parallel", "mul", "fp32")
    line, _ = drive(cell, system)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("name", ["fp32-fig9-64Mi", "int32-fig9-4Mi"])
def test_the_control_is_not_correct(name):
    cell = small_cell(name)
    line, _ = drive(cell, control.Control(harness.Ufuncs(cell.config)))
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
    system = harness.Ufuncs(cell.config)
    harness.warm_up(system, traffic.make(cell.mix, cell.config, 1))
    fault(monkeypatch)                          # compiled before breaking
    line, _ = drive(cell, system)
    assert not line["correct"]
    assert line["failed"] > 0


def test_reference_and_control_differ_as_stated():
    rng = traffic.rng_for(5)
    x = traffic.fp_operands(rng, "float32", 4096, -4, 5)
    y = traffic.fp_operands(rng, "float32", 4096, -4, 5)
    for op in ("fp_add", "fp_mul", "fp_div"):
        want = reference.reference(op, x, y)
        low = reference.control(op, x, y)
        assert np.all(low & 0xFFFF == 0)
        assert reference.mismatched_rows(op, want, low) > 4000
    xi = traffic.int_operands(rng, "uint32", 4096, 0)
    yi = traffic.int_operands(rng, "uint32", 4096, 1)
    assert reference.mismatched_rows(
        "add", reference.reference("add", xi, yi),
        reference.control("add", xi, yi)) > 1000
    q, r = reference.reference("div", xi, yi)
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
                        system=harness.Ufuncs(cell.config), t_process=0.0,
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
