"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run`` prints ``name,us_per_call,derived`` CSV rows:
  * per-algorithm NOR-cycle latencies -> microseconds on the memristive
    device model (paper Tables / Fig. 9 substrate),
  * Karatsuba crossover (paper §3.2 fn. 3),
  * variable-normalization overhead (paper §4.4),
  * Fig. 9 throughput / throughput-per-Watt vs the GPU roofline,
  * PIM executor kernel wall-time (element-parallel emulation rate), for
    both the levelized pipeline and the gate-serial baseline.

``--json PATH`` additionally writes the rows as machine-readable JSON
(see BENCH_<n>.json checked in per PR for the perf trajectory);
``--only PREFIX`` restricts to row-name prefixes (e.g. ``--only kernel``
for the smoke invocation wired into the test suite); ``--compare
BENCH_<n>.json`` prints a per-row delta table against a previous run and
exits nonzero when any tracked ``kernel/`` row regresses by more than
``--threshold`` (default 20%) -- the perf-regression gate future PRs run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.device_model import PIM_DEFAULT
from repro.runtime import telemetry


def _rate(n: int, dt: float):
    """rows/s, guarded: a zero duration (possible only with a broken or
    too-coarse clock) reports None instead of a nonsense inf rate."""
    return round(n / dt) if dt > 0 else None


def _best_of(fn, reps: int = 8) -> float:
    """min-of-reps wall time via the monotonic high-resolution clock
    (time.time() is coarse enough on some hosts to return 0 deltas)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _lat_fields(samples_s) -> dict:
    """p50/p99 of the per-call wall samples, in microseconds.  Percentiles
    ride next to the min-of-reps headline so the checked-in BENCH_<n>.json
    records each row's jitter, not just its floor."""
    s = np.asarray(samples_s, dtype=float) * 1e6
    return {"lat_p50_us": round(float(np.percentile(s, 50)), 1),
            "lat_p99_us": round(float(np.percentile(s, 99)), 1)}


def _model_fields(counters: dict, calls: int) -> dict:
    """Analytical device cost per call from the drained telemetry model
    counters (DESIGN.md §15): NOR cycles on the memristive device model
    and the command-energy estimate.  Empty when the measured path never
    dispatched through the instrumented executors (e.g. pure numpy)."""
    calls = max(calls, 1)
    cycles = counters.get("pim.model.cycles", 0) / calls
    if not cycles:
        return {}
    epj = counters.get("pim.model.energy_pj", 0.0) / calls
    return {"model_cycles": int(round(cycles)),
            "model_us": round(cycles * PIM_DEFAULT.cycle_ns * 1e-3, 3),
            "model_energy_nj": round(epj * 1e-3, 4)}


def _measured(fn, reps: int = 8):
    """One benchmark measurement: min-of-reps wall time plus the derived
    fields every tracked row now carries -- wall p50/p99 and the modeled
    device cycles/energy drained from the telemetry registry over the
    same ``reps`` calls."""
    telemetry.drain_model_counters()            # window starts clean
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    counters = telemetry.drain_model_counters()
    return min(samples), {**_lat_fields(samples),
                          **_model_fields(counters, reps)}


def _model_of_one(fn) -> dict:
    """Modeled cost of a single call (for rows whose timing loop mixes
    two configurations and cannot attribute the drained counters)."""
    telemetry.drain_model_counters()
    fn()
    return _model_fields(telemetry.drain_model_counters(), 1)


def _sharded_row_subprocess(row_name):
    """Measure one sharded 1M-row kernel row in a child process with a
    forced 4-device CPU backend (CPU parents only: the child is pinned to
    the CPU, so it never contends for an accelerator the parent holds).
    Isolation is the honest methodology: the XLA device-split flag divides
    the host's thread pool for *every* array op in the process, so
    measuring the unsharded rows under it would tax them with the sharded
    row's configuration (and the flag only takes effect before jax
    initializes anyway).  ``row_name`` is matched exactly (several sharded
    rows share a name prefix)."""
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run",
             "--only", row_name, "--json", tmp.name],
            cwd=repo, env=env, capture_output=True, text=True, timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(
                f"sharded benchmark subprocess failed: {proc.stderr[-800:]}")
        with open(tmp.name) as f:
            doc = json.load(f)
    (row,) = [r for r in doc["rows"] if r["name"] == row_name]
    us = row.pop("us_per_call")
    name = row.pop("name")
    return name, us, row


def _warm_start_probe(cache_dir: str) -> None:
    """Child-process body for the warm-start rows (``--warm-start-probe``):
    point the ufunc frontend at ``cache_dir``, warm from disk, then run the
    mixed 8-op serving suite (uint16 + fp16 add/sub/mul/div, 1024 rows
    each) once -- the time-to-first-result a fresh server pays.  On an
    empty directory this is the cold path (levelize + trace + XLA compile
    for all 8 programs, artifacts written); on a populated one it is the
    warm path (schedules + AOT executables deserialized, zero recompiles).
    Prints one JSON object on stdout; a blake2b digest of all outputs lets
    the parent assert cold and warm runs are bit-identical.  JAX's own
    persistent compilation cache stays off in both probes, so "cold"
    compiles everything and "warm" measures the artifact cache alone."""
    import hashlib

    import jax
    jax.config.update("jax_enable_compilation_cache", False)

    from repro import pim_ufunc as pim
    from repro.kernels import ops as kops
    from repro.runtime import telemetry

    t0 = time.perf_counter()
    pim.configure(cache_dir=cache_dir)
    pim._ensure_artifact_cache()
    counts = kops.artifact_cache().warm()
    warm_us = (time.perf_counter() - t0) * 1e6

    rng = np.random.default_rng(0)
    n = 1024
    x = rng.integers(0, 1 << 16, n).astype(np.uint16)
    y = rng.integers(0, 1 << 16, n).astype(np.uint16)
    d = rng.integers(1, 1 << 16, n).astype(np.uint16)

    def fp16(k):
        return (rng.integers(10, 21, k).astype(np.uint16) << 10 |
                rng.integers(0, 1 << 10, k).astype(np.uint16)
                ).view(np.float16)

    fa, fb, fd = fp16(n), fp16(n), fp16(n)
    suite = [("add", x, y), ("sub", x, y), ("mul", x, y), ("div", x, d),
             ("fp_add", fa, fb), ("fp_sub", fa, fb), ("fp_mul", fa, fb),
             ("fp_div", fa, fd)]
    h = hashlib.blake2b(digest_size=8)
    t1 = time.perf_counter()
    for op, a, b in suite:
        h.update(np.asarray(getattr(pim, op)(a, b)).tobytes())
    first_us = (time.perf_counter() - t1) * 1e6
    reg = telemetry.REGISTRY
    json.dump({
        "total_us": round(warm_us + first_us, 1),
        "warm_us": round(warm_us, 1),
        "first_runs_us": round(first_us, 1),
        "digest": h.hexdigest(),
        "schedules": counts["schedules"],
        "executables": counts["executables"],
        "levelized": int(reg.counter("pim.cache.levelized")),
        "disk_hits": int(reg.counter("pim.cache.disk_hits")),
        "disk_writes": int(reg.counter("pim.cache.disk_writes")),
    }, sys.stdout)
    print()


def _warm_start_rows(only: str = ""):
    """Cold vs warm process start for the mixed 8-op serving suite
    (DESIGN.md §16).  Two identical child processes share one fresh cache
    directory: the first (cold) levelizes and compiles all 8 programs and
    persists the artifacts; the second (warm) restores them via
    ``ArtifactCache.warm()``.  Each child reports time-to-first-result for
    the whole suite; the warm row carries ``cold_start_us`` and the
    tracked ``speedup_vs_cold`` (acceptance: >= 10x).  The children need
    the device, so :func:`collect_rows` runs them before this process
    initialises JAX (one process per chip)."""
    import subprocess
    import tempfile

    rows = []
    names = ("kernel/warm_start_mixed8_cold", "kernel/warm_start_mixed8_warm")
    if only and not any(nm.startswith(only) for nm in names):
        return rows
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    with tempfile.TemporaryDirectory() as cache_dir:
        def probe():
            proc = subprocess.run(
                [sys.executable, "-m", "benchmarks.run",
                 "--warm-start-probe", cache_dir],
                cwd=repo, env=env, capture_output=True, text=True,
                timeout=1200)
            if proc.returncode != 0:
                raise RuntimeError("warm-start probe failed: "
                                   f"{proc.stderr[-800:]}")
            return json.loads(proc.stdout.strip().splitlines()[-1])

        cold = probe()
        warm = probe()
    if warm["digest"] != cold["digest"]:
        raise RuntimeError(
            "warm-start outputs diverged from cold run: "
            f"{warm['digest']} != {cold['digest']}")
    common = {"requests": 8, "programs": 8, "rows_per_request": 1024}
    rows.append((names[0], cold["total_us"], dict(
        common, first_runs_us=cold["first_runs_us"],
        levelized=cold["levelized"], disk_writes=cold["disk_writes"])))
    rows.append((names[1], warm["total_us"], dict(
        common, first_runs_us=warm["first_runs_us"],
        warm_us=warm["warm_us"], schedules=warm["schedules"],
        executables=warm["executables"], levelized=warm["levelized"],
        disk_hits=warm["disk_hits"],
        cold_start_us=cold["total_us"],
        speedup_vs_cold=round(cold["total_us"] / warm["total_us"], 1))))
    if only:
        rows = [r for r in rows if r[0].startswith(only)]
    return rows


def _kernel_rows(only: str = ""):
    """Wall-time of the end-to-end executor pipeline on fp16 element-
    parallel addition: 8192 rows levelized vs gate-serial, plus the scale
    path -- 1 Mi rows through the chunked streaming executor, unsharded and
    row-sharded over every available device (DESIGN.md §8)."""
    import jax

    from repro.core import bitserial_fp
    from repro.core.floatfmt import FP16
    from repro.kernels import ops as kops

    prog = bitserial_fp.build_fp_add(FP16)
    rng = np.random.default_rng(0)
    n = 8192
    x = FP16.random_bits(rng, n, emin=10, emax=20).astype(np.uint64)
    y = FP16.random_bits(rng, n, emin=10, emax=20).astype(np.uint64)

    def bench(**kw):
        # the warm-up call is timed as compile_us: first-call latency for
        # this config in this process (levelize + trace + XLA compile when
        # cold; near the steady-state call when the artifact cache or a
        # sibling row already compiled it) -- the cold-start figure the
        # persistent artifact cache attacks (DESIGN.md §16)
        t0 = time.perf_counter()
        kops.run_program(prog, {"x": x, "y": y}, n, **kw)   # warm up
        compile_us = round((time.perf_counter() - t0) * 1e6, 1)
        # min-of-20: this host-shared CPU jitters 30-40% between runs, and
        # the 8k row is the PR-over-PR perf trajectory anchor
        dt, extra = _measured(
            lambda: kops.run_program(prog, {"x": x, "y": y}, n, **kw),
            reps=20)
        return dt, {**extra, "compile_us": compile_us}

    rows = []

    def want_row(name):
        """Row-granular gating (name extends the --only prefix), so
        single-row invocations don't pay for their siblings."""
        return not only or name.startswith(only)

    _base = []

    def base_dt():
        """The tracked ref-slots wall time; benched lazily exactly once
        (several rows report their ratio against it)."""
        if not _base:
            _base.append(bench(backend="ref"))
        return _base[0][0]

    if want_row("kernel/fp16_add_8k_rows"):
        # tracked row: the default executor path (contiguous-slot schedule,
        # scan executors, butterfly bridges -- DESIGN.md §9)
        dt = base_dt()
        sched = kops.program_schedule(prog)
        rows.append(("kernel/fp16_add_8k_rows", dt * 1e6, {
            "rows_per_s": _rate(n, dt), "backend": "ref", "levelized": 1,
            "schedule": "slots", "levels": int(sched.n_levels),
            "level_width": int(sched.width), "cells": int(sched.n_cells),
            "copy_gates": int(sched.copy_gates), **_base[0][1]}))
    if want_row("kernel/fp16_add_8k_rows_dense"):
        dtd, exd = bench(backend="ref", schedule="dense")
        rows.append(("kernel/fp16_add_8k_rows_dense", dtd * 1e6, {
            "rows_per_s": _rate(n, dtd), "backend": "ref", "levelized": 1,
            "schedule": "dense",
            "speedup_slots": round(dtd / base_dt(), 2), **exd}))
    if want_row("kernel/fp16_add_8k_rows_serial"):
        dts, exs = bench(backend="ref", levelized=False)
        rows.append(("kernel/fp16_add_8k_rows_serial", dts * 1e6, {
            "rows_per_s": _rate(n, dts), "backend": "ref", "levelized": 0,
            "speedup_levelized": round(dts / base_dt(), 2), **exs}))
    if want_row("kernel/fp16_add_8k_rows_pallas"):
        dtp, exp_ = bench(backend="pallas", schedule="dense")
        rows.append(("kernel/fp16_add_8k_rows_pallas", dtp * 1e6, {
            "rows_per_s": _rate(n, dtp), "backend": "pallas",
            "levelized": 1, "schedule": "dense", **exp_}))
    if want_row("kernel/fp16_add_8k_rows_pallas_fused"):
        # the slot-schedule pallas kernel: scatter-free scan body, one
        # fused pallas_call -- the row that must be <= the tracked ref row
        dtf, exf = bench(backend="pallas", schedule="slots")
        rows.append(("kernel/fp16_add_8k_rows_pallas_fused", dtf * 1e6, {
            "rows_per_s": _rate(n, dtf), "backend": "pallas",
            "levelized": 1, "schedule": "slots",
            "vs_ref": round(dtf / base_dt(), 3), **exf}))
    if want_row("kernel/fp16_add_8k_rows_rows64"):
        # the paired-uint32 word layout (ExecPlan layout="rows64",
        # DESIGN.md §11): 64 rows per word-pair, halved trailing word axis
        dt64, ex64 = bench(plan=kops.make_plan(backend="ref",
                                               layout="rows64"))
        rows.append(("kernel/fp16_add_8k_rows_rows64", dt64 * 1e6, {
            "rows_per_s": _rate(n, dt64), "backend": "ref", "levelized": 1,
            "schedule": "slots", "layout": "rows64",
            "vs_rows32": round(dt64 / base_dt(), 3), **ex64}))
    if want_row("kernel/fp16_add_8k_rows_verified"):
        # verified execution with checking on but no faults injected: the
        # retry/spot-check scaffolding of the verified dispatcher.  The
        # XOR check plane is emitted on the device (pim_exec.check_words)
        # and only when a FaultModel is present alongside the policy, so
        # verify-only plans never pay a fold at all (DESIGN.md §14).
        # Acceptance: <10% overhead over the ref row; a plan with
        # FaultModel/verify unset pays exactly 0% (it never enters the
        # verified dispatcher -- tests/test_faults.py pins that).
        # overhead_vs_base is the median of per-pair ratios from
        # call-by-call interleaving (order alternated to cancel order
        # bias): this host's 30-40% drift between separate measurement
        # windows would otherwise swamp the few-percent real cost.
        pln_v = kops.make_plan(backend="ref", verify=True)
        pln_b = kops.make_plan(backend="ref")

        def _one(p):
            t0 = time.perf_counter()
            kops.run_program(prog, {"x": x, "y": y}, n, plan=p)
            return time.perf_counter() - t0

        _one(pln_v), _one(pln_b)                      # warm up
        vts, ratios = [], []
        for i in range(40):
            if i % 2:
                v = _one(pln_v)
                b = _one(pln_b)
            else:
                b = _one(pln_b)
                v = _one(pln_v)
            vts.append(v)
            ratios.append(v / b)
        dtv = min(vts)
        rows.append(("kernel/fp16_add_8k_rows_verified", dtv * 1e6, {
            "rows_per_s": _rate(n, dtv), "backend": "ref", "levelized": 1,
            "schedule": "slots", "verified": 1,
            "overhead_vs_base": round(float(np.median(ratios)) - 1.0, 3),
            **_lat_fields(vts), **_model_of_one(lambda: _one(pln_v))}))

    # straight-line static-slice emission (the Mosaic-lowerable shape):
    # segmented jaxpr chain on ref, fully unrolled kernel on pallas.  On
    # CPU the unrolled forms pay per-op dispatch/interpret overhead; these
    # rows track that gap honestly (hardware is the target).
    if want_row("kernel/fp16_add_8k_rows_static"):
        dss, exss = bench(backend="ref", schedule="slots-static")
        rows.append(("kernel/fp16_add_8k_rows_static", dss * 1e6, {
            "rows_per_s": _rate(n, dss), "backend": "ref", "levelized": 1,
            "schedule": "slots-static", **exss}))
    if want_row("kernel/fp16_add_8k_rows_pallas_static"):
        dsp, exsp = bench(backend="pallas", schedule="slots-static")
        rows.append(("kernel/fp16_add_8k_rows_pallas_static", dsp * 1e6, {
            "rows_per_s": _rate(n, dsp), "backend": "pallas",
            "levelized": 1, "schedule": "slots-static", **exsp}))

    # ---- compound-program fusion: packed-domain reduction trees
    # (DESIGN.md §13).  speedup_vs_unfused is the tracked claim: the fused
    # tree (one pack, log2(K) packed-domain add levels, one scalar unpack)
    # vs the identical pairing through per-op value-domain round trips.
    # Measured as the median of per-pair ratios from call-by-call
    # interleaving (order alternated) -- same methodology as the verified
    # row: this host's 30-40% drift between separate measurement windows
    # would otherwise swamp the real fused-vs-unfused gap.
    def _fused_vs_unfused(run_fused, run_unfused, pairs=8):
        run_fused(), run_unfused()                            # warm up
        fts, ratios = [], []
        for i in range(pairs):
            if i % 2:
                f = _best_of(run_fused, reps=1)
                u = _best_of(run_unfused, reps=1)
            else:
                u = _best_of(run_unfused, reps=1)
                f = _best_of(run_fused, reps=1)
            fts.append(f)
            ratios.append(u / f)
        return min(fts), float(np.median(ratios)), fts

    if want_row("kernel/fp16_dot_8k"):
        from repro import pim_ufunc as pim
        xd = x.copy()
        yd = y.copy()
        run_dot = lambda: pim.dot(xd, yd, fmt="fp16", backend="ref")
        dtd, ratio, dts_s = _fused_vs_unfused(
            run_dot,
            lambda: pim.dot(xd, yd, fmt="fp16", backend="ref",
                            fused=False))
        rows.append(("kernel/fp16_dot_8k", dtd * 1e6, {
            "rows_per_s": _rate(n, dtd), "backend": "ref", "levelized": 1,
            "schedule": "slots", "fused": 1, "reduce_rows": n,
            "speedup_vs_unfused": round(ratio, 2),
            **_lat_fields(dts_s), **_model_of_one(run_dot)}))
    if want_row("kernel/i16_gemv_64x1k"):
        from repro import pim_ufunc as pim
        gm, gk = 64, 1024
        ga = rng.integers(0, 1 << 16, (gm, gk)).astype(np.uint64)
        gx = rng.integers(0, 1 << 16, gk).astype(np.uint64)
        run_gemv = lambda: pim.gemv(ga, gx, width=16, backend="ref")
        dtg, gratio, gts_s = _fused_vs_unfused(
            run_gemv,
            lambda: pim.gemv(ga, gx, width=16, backend="ref",
                             fused=False), pairs=5)
        rows.append(("kernel/i16_gemv_64x1k", dtg * 1e6, {
            "rows_per_s": _rate(gm * gk, dtg), "backend": "ref",
            "levelized": 1, "schedule": "slots", "fused": 1,
            "m": gm, "k": gk,
            "speedup_vs_unfused": round(gratio, 2),
            **_lat_fields(gts_s), **_model_of_one(run_gemv)}))
    if want_row("kernel/i16_gemv_64x1k_verified"):
        # the packed reduction tree under verified execution (DESIGN.md
        # §14): per-level on-device check words + the host compare, no
        # faults injected.  Same interleaved median-of-pair-ratios
        # methodology as the fp16 verified row (host drift would swamp
        # the real cost in separate windows).
        from repro import pim_ufunc as pim
        gm, gk = 64, 1024
        ga = rng.integers(0, 1 << 16, (gm, gk)).astype(np.uint64)
        gx = rng.integers(0, 1 << 16, gk).astype(np.uint64)

        def _one_gemv(verified):
            t0 = time.perf_counter()
            pim.gemv(ga, gx, width=16, backend="ref",
                     verify=True if verified else None)
            return time.perf_counter() - t0

        _one_gemv(True), _one_gemv(False)             # warm up
        vts, ratios = [], []
        for i in range(8):
            if i % 2:
                v = _one_gemv(True)
                b = _one_gemv(False)
            else:
                b = _one_gemv(False)
                v = _one_gemv(True)
            vts.append(v)
            ratios.append(v / b)
        dtgv = min(vts)
        rows.append(("kernel/i16_gemv_64x1k_verified", dtgv * 1e6, {
            "rows_per_s": _rate(gm * gk, dtgv), "backend": "ref",
            "levelized": 1, "schedule": "slots", "fused": 1,
            "verified": 1, "m": gm, "k": gk,
            "overhead_vs_base": round(float(np.median(ratios)) - 1.0, 3),
            **_lat_fields(vts),
            **_model_of_one(lambda: _one_gemv(True))}))

    # ---- scale path: 1 Mi rows, chunked streaming +/- row sharding
    nm = 1 << 20
    chunk = kops.DEFAULT_CHUNK_ROWS

    def bench_stream(mesh, layout="rows32"):
        xm = FP16.random_bits(rng, nm, emin=10, emax=20).astype(np.uint64)
        ym = FP16.random_bits(rng, nm, emin=10, emax=20).astype(np.uint64)
        stream_plan = kops.make_plan(backend="ref", chunk_rows=chunk,
                                     mesh=mesh, layout=layout)
        run = lambda: kops.run_program_streaming(
            prog, {"x": xm, "y": ym}, nm, stream_plan)
        run()                               # warm up (compiles chunk shape)
        return _measured(run, reps=3)

    if want_row("kernel/fp16_add_1M_rows_stream"):
        dt1, ex1 = bench_stream(mesh=None)
        rows.append(("kernel/fp16_add_1M_rows_stream", dt1 * 1e6, {
            "rows_per_s": _rate(nm, dt1), "backend": "ref", "levelized": 1,
            "chunk_rows": chunk, "n_devices": 1, **ex1}))

    def sharded_row(name, layout):
        # a one-device CPU parent measures in a forced 4-device CPU child;
        # every other backend measures in-process over its real devices
        n_dev = len(jax.devices())
        if n_dev == 1 and jax.default_backend() == "cpu" and \
                "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            return _sharded_row_subprocess(name)
        if n_dev < 4:
            raise RuntimeError(
                f"{name} shards over 4 devices; this "
                f"{jax.default_backend()} backend has {n_dev}")
        mesh = kops.row_mesh()
        dt4, ex4 = bench_stream(mesh=mesh, layout=layout)
        return (name, dt4 * 1e6, {
            "rows_per_s": _rate(nm, dt4), "backend": "ref",
            "levelized": 1, "chunk_rows": chunk, "layout": layout,
            "n_devices": int(mesh.devices.size), **ex4})

    if want_row("kernel/fp16_add_1M_rows_sharded"):
        rows.append(sharded_row("kernel/fp16_add_1M_rows_sharded",
                                "rows32"))
    if want_row("kernel/fp16_add_1M_rows64_sharded"):
        # the sharded scale path under the paired word layout: half the
        # words per shard for the same 1M rows
        rows.append(sharded_row("kernel/fp16_add_1M_rows64_sharded",
                                "rows64"))
    return rows


def _serve_rows(only: str = ""):
    """Mixed-traffic serving throughput (ISSUE 4 acceptance row): the same
    interleaved request stream -- 8 distinct programs (uint16 add/sub/mul/
    div + fp16 add/sub/mul/div), round-robin -- executed two ways: the
    per-request serial loop (``--pim-stdin``'s execution model, one gate
    program per request) vs the batched planner/coalescer
    (``runtime/pim_batch``, ``--pim-serve``'s model: group by program
    content hash, execute each group as one packed state, pipelined).
    Both paths pay identical parse/validation work per request; the only
    difference is row-axis coalescing."""
    from repro import pim_ufunc as pim
    from repro.runtime import pim_batch

    rng = np.random.default_rng(0)
    n_req_per_op = 8
    rows_per_req = 1024

    def fp16(n):
        # mid-range exponents: the paper excludes overflow/underflow
        return (rng.integers(10, 21, n).astype(np.uint16) << 10 |
                rng.integers(0, 1 << 10, n).astype(np.uint16)
                ).view(np.float16)

    traffic = []
    for _ in range(n_req_per_op):
        n = rows_per_req
        x = rng.integers(0, 1 << 16, n).astype(np.uint16)
        y = rng.integers(0, 1 << 16, n).astype(np.uint16)
        d = rng.integers(1, 1 << 16, n).astype(np.uint16)
        fa, fb, fd = fp16(n), fp16(n), fp16(n)   # fd nonzero (exp >= 10)
        traffic += [("add", x, y), ("sub", x, y), ("mul", x, y),
                    ("div", x, d), ("fp_add", fa, fb), ("fp_sub", fa, fb),
                    ("fp_mul", fa, fb), ("fp_div", fa, fd)]
    total = len(traffic) * rows_per_req

    def serial():
        for op, x, y in traffic:
            getattr(pim, op)(x, y)

    runtime = pim_batch.BatchRuntime(pin_cap=16)

    def batched():
        runtime.execute([pim.prepare(op, x, y) for op, x, y in traffic])

    serial()                    # warm: compile all 8 programs, both shapes
    batched()
    dts = _best_of(serial, reps=3)
    dtb = _best_of(batched, reps=3)
    runtime.close()

    # the same mixed traffic -- grown with compound requests (a fused
    # depth-3 expression through the runtime plus packed-tree dot/gemv
    # calls, DESIGN.md §13/§14) -- under a nonzero injected fault rate
    # with verified execution: the cost of serving *correct* answers off
    # faulty media across every execution path the verifier covers.
    # overhead_vs_clean compares against the identical grown stream with
    # verification off, so the ratio isolates the fault-tolerance cost.
    from repro.kernels import ops as kops
    from repro.runtime.faults import FaultModel
    ex_x = rng.integers(0, 1 << 8, rows_per_req).astype(np.uint8)
    ex_y = rng.integers(1, 1 << 8, rows_per_req).astype(np.uint8)
    ex_z = rng.integers(0, 1 << 8, rows_per_req).astype(np.uint8)
    dot_x = rng.integers(0, 256, 256).astype(np.uint8)
    dot_y = rng.integers(0, 256, 256).astype(np.uint8)
    gemv_a = rng.integers(0, 1 << 16, (4, 128)).astype(np.uint64)
    gemv_x = rng.integers(0, 1 << 16, 128).astype(np.uint64)

    def _expr_prep():
        lx, ly, lz = pim.lazy(ex_x), pim.lazy(ex_y), pim.lazy(ex_z)
        return pim.sub(pim.add(pim.mul(lx, ly), lz), lx).fuse()

    def _grown(rt):
        rs = rt.execute([pim.prepare(op, x, y) for op, x, y in traffic]
                        + [_expr_prep()])
        bad = [r for r in rs if r.error is not None]
        if bad:
            raise RuntimeError(f"serving failed: {bad[0].error}")
        pim.dot(dot_x, dot_y)
        pim.gemv(gemv_a, gemv_x, width=16)

    crt = pim_batch.BatchRuntime(pin_cap=16)
    _grown(crt)                 # warm the compound programs
    dtc = _best_of(lambda: _grown(crt), reps=3)
    crt.close()
    frt = pim_batch.BatchRuntime(pin_cap=16)
    with pim.options(faults=FaultModel(seed=7, p_flip=5e-4), verify=True):
        _grown(frt)             # warm (+ proves every request recovers)
        dtf = _best_of(lambda: _grown(frt), reps=3)
    st = frt.stats
    frt.close()
    kops.drain_health()
    total_grown = total + rows_per_req + dot_x.size + gemv_a.size
    common = {"requests": len(traffic), "programs": 8,
              "rows_per_request": rows_per_req}
    return [
        ("serve/mixed_8op_serial", dts * 1e6,
         dict(common, rows_per_s=_rate(total, dts))),
        ("serve/mixed_8op_batched", dtb * 1e6,
         dict(common, rows_per_s=_rate(total, dtb),
              speedup_vs_serial=round(dts / dtb, 2))),
        ("serve/mixed_8op_faulty", dtf * 1e6,
         dict(common, rows_per_s=_rate(total_grown, dtf),
              p_flip=5e-4, verified=1, compound_requests=3,
              faults_detected=st.faults_detected,
              faults_corrected=st.faults_corrected,
              retries=st.retries,
              overhead_vs_clean=round(dtf / dtc - 1.0, 3))),
    ]


def collect_rows(only: str = "") -> list:
    """All benchmark rows as (name, us_per_call, derived-dict) tuples."""
    def want(prefix):
        return not only or prefix.startswith(only) or only.startswith(prefix)

    # first: the warm-start children need the device, which this process
    # holds from its first JAX computation on
    rows = _warm_start_rows(only) if want("kernel") else []

    if want("cycles"):
        from . import cycles
        for r in cycles.rows():
            us = r["nor_cycles"] * PIM_DEFAULT.cycle_ns * 1e-3
            rows.append((f"cycles/{r['op'].replace(' ', '_')}", us, {
                "steps": r["steps"], "nor": r["nor_cycles"],
                "nor9": r["nor_cycles_norm9"], "cells": r["cells"]}))
        from repro.core import bitserial_fp as bsf64
        from repro.core.floatfmt import FP64
        c64 = bsf64.build_fp_add(FP64).cost()
        rows.append(("cycles/serial_fp64_add",
                     c64.nor_gates * PIM_DEFAULT.cycle_ns * 1e-3,
                     {"steps": c64.abstract_steps, "nor": c64.nor_gates}))

    if want("karatsuba"):
        from . import karatsuba
        for r in karatsuba.rows():
            us = r["karatsuba_nor"] * PIM_DEFAULT.cycle_ns * 1e-3
            rows.append((f"karatsuba/N{r['N']}", us,
                         {"speedup_vs_shift_add": r["speedup"]}))
        rows.append(("karatsuba/crossover", 0.0, {"N": karatsuba.crossover()}))

    if want("varnorm"):
        from . import varshift
        for r in varshift.rows():
            us = r["var_norm_nor"] * PIM_DEFAULT.cycle_ns * 1e-3
            rows.append((f"varnorm/Nx{r['Nx']}", us, {
                "overhead_pct": r["overhead_pct"],
                "naive_overhead_pct": r["naive_overhead_pct"]}))

    if want("fig9"):
        from . import fig9
        for r in fig9.rows():
            rows.append((f"fig9/{r['op'].replace(' ', '_')}", 0.0, {
                "pim_gops": r["pim_gops"], "gpu_gops": r["gpu_gops"],
                "speedup": r["speedup"], "energy_ratio": r["energy_ratio"]}))

    if want("offload"):
        from repro.configs import registry
        from repro.core.offload import decode_step_plan
        for arch in ("rwkv6-1.6b", "qwen3-8b"):
            plans = decode_step_plan(registry.get(arch), batch=128, seq=32768)
            n_off = sum(p.offload for p in plans)
            tot_tpu = sum(p.tpu_us for p in plans)
            tot_pim = sum(p.pim_us if p.offload else p.tpu_us for p in plans)
            rows.append((f"offload/{arch}", tot_pim, {
                "classes_offloaded": f"{n_off}/{len(plans)}",
                "elementwise_us_tpu": round(tot_tpu, 1)}))

    if want("kernel"):
        rows.extend(_kernel_rows(only))
    if want("serve"):
        rows.extend(_serve_rows(only))
    if only:
        rows = [r for r in rows if r[0].startswith(only)]
    return rows


def compare_rows(rows, baseline_path: str, threshold: float = 0.20,
                 complete: bool = True):
    """Per-row delta table against a previous BENCH_<n>.json.

    Rows are matched by name; only rows present in both runs with nonzero
    wall times are ratioed.  *Tracked* rows (``kernel/`` wall-time rows --
    the executor perf trajectory) whose time regresses by more than
    ``threshold`` are returned as failures; derived-model rows (cycles/
    karatsuba/fig9/...) are shown for drift but never gate.  When
    ``complete`` (a full run, no ``--only`` filter), a tracked baseline
    row that the current run no longer produces is itself a failure --
    dropping or renaming a tracked row must not pass the gate vacuously.
    """
    with open(baseline_path) as f:
        base = {r["name"]: r for r in json.load(f)["rows"]}
    failures = []
    print(f"\ncomparison vs {baseline_path} "
          f"(gate: kernel/* rows, +{threshold:.0%}):")
    print(f"{'row':44s} {'base_us':>12s} {'now_us':>12s} {'delta':>8s}")
    current = set()
    for name, us, _ in rows:
        current.add(name)
        old = base.get(name)
        if old is None:
            print(f"{name:44s} {'-':>12s} {us:12.1f} {'new':>8s}")
            continue
        old_us = old.get("us_per_call", 0.0)
        if not old_us or not us:
            continue
        delta = us / old_us - 1.0
        flag = ""
        if name.startswith("kernel/") and delta > threshold:
            flag = "  REGRESSED"
            failures.append((name, old_us, us, delta))
        print(f"{name:44s} {old_us:12.1f} {us:12.1f} {delta:+8.1%}{flag}")
    if complete:
        for name in sorted(base):
            if name.startswith("kernel/") and name not in current:
                print(f"{name:44s} {'?':>12s} {'-':>12s} "
                      f"{'MISSING':>8s}  REGRESSED")
                failures.append((name, base[name].get("us_per_call", 0.0),
                                 float("nan"), float("inf")))
    return failures


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH",
                    help="also write rows as machine-readable JSON")
    ap.add_argument("--only", default="",
                    help="restrict to row-name prefix (e.g. 'kernel')")
    ap.add_argument("--compare", metavar="BASELINE",
                    help="compare against a previous BENCH_<n>.json and "
                         "exit nonzero when a tracked kernel/ row regresses "
                         "past --threshold (the perf-regression gate)")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="allowed fractional slowdown for tracked rows "
                         "under --compare (default 0.20)")
    ap.add_argument("--warm-start-probe", metavar="DIR",
                    help=argparse.SUPPRESS)   # child mode for the
    #                                           warm-start rows
    ap.add_argument("--devices", type=int, default=0,
                    help="force an N-device CPU backend in this process "
                         "(0 = leave the backend alone; the sharded kernel "
                         "row then measures itself in a 4-device child)")
    args = ap.parse_args(argv)

    if args.warm_start_probe:
        _warm_start_probe(args.warm_start_probe)
        return

    # XLA can split a CPU host into N devices, but only if the flag is set
    # before jax initializes (a no-op when jax was already imported)
    if args.devices > 1 and "jax" not in sys.modules \
            and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}").strip()
    from repro.runtime import compile_cache
    compile_cache.enable()

    rows = collect_rows(args.only)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        dstr = ";".join(f"{k}={v}" for k, v in derived.items())
        print(f"{name},{us:.3f},{dstr}")

    if args.json:
        doc = {
            "meta": {
                "suite": "aritpim-repro",
                "tier1": "benchmarks.run",
                "python": sys.version.split()[0],
                "device_cycle_ns": PIM_DEFAULT.cycle_ns,
            },
            "rows": [{"name": n, "us_per_call": round(us, 3), **d}
                     for n, us, d in rows],
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")

    if args.compare:
        failures = compare_rows(rows, args.compare, args.threshold,
                                complete=not args.only)
        if failures:
            print(f"\n{len(failures)} tracked row(s) regressed more than "
                  f"{args.threshold:.0%} (or went missing):")
            for name, old_us, us, delta in failures:
                print(f"  {name}: {old_us:.1f}us -> {us:.1f}us "
                      f"({delta:+.1%})")
            sys.exit(1)
        print("\nperf gate: OK")


if __name__ == "__main__":
    main()
