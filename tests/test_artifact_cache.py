"""Persistent compiled-artifact cache (DESIGN.md §16): disk round-trips
are bit-exact against the numpy oracle across layouts and schedules,
corruption and version skew silently recompute, the size cap evicts
least-recently-used artifacts, concurrent writers on one directory never
tear files, and ``warm()`` (in-process and via a second ``--pim-serve``
replica) restores a process to hot with zero recompiles."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import pim_ufunc as pim
from repro.kernels import ops as kops
from repro.kernels import plan as kplan
from repro.runtime import telemetry
from repro.runtime.artifact_cache import ArtifactCache, _MAGIC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _c(name: str) -> int:
    return int(telemetry.REGISTRY.counter(f"pim.cache.{name}"))


@pytest.fixture
def cache(tmp_path):
    """A fresh on-disk cache installed process-wide, uninstalled (and the
    in-memory compiled cache cleared) afterwards so tests stay isolated."""
    c = ArtifactCache(tmp_path / "cache")
    kops.set_artifact_cache(c)
    try:
        yield c
    finally:
        kops.set_artifact_cache(None)
        kops.clear_compiled_cache()
        kplan.clear_tuned()


def _fp16_operands(rng, n):
    # mid-range exponents: products/sums stay normal (no NaN/Inf/subnormal)
    def bits(k):
        return (rng.integers(10, 21, k).astype(np.uint16) << 10 |
                rng.integers(0, 1 << 10, k).astype(np.uint16)
                ).view(np.float16)
    return bits(n), bits(n)


def test_disk_roundtrip_bit_exact_all_layouts_schedules(cache):
    """Populate the disk tier, drop all in-memory compiled state, and
    re-execute: every (layout x schedule) combination must come back from
    disk (zero fresh levelizations) bit-identical to the numpy oracle."""
    rng = np.random.default_rng(0)
    n = 256
    x = rng.integers(0, 1 << 16, n).astype(np.uint16)
    y = rng.integers(0, 1 << 16, n).astype(np.uint16)
    fx, fy = _fp16_operands(rng, n)
    combos = [(lay, sch) for lay in ("rows32", "rows64")
              for sch in ("slots", "slots-static", "dense")]

    def run_all():
        outs = []
        for lay, sch in combos:
            outs.append(pim.add(x, y, width=16, layout=lay, schedule=sch))
            outs.append(pim.fp_mul(fx, fy, layout=lay, schedule=sch))
        return outs

    run_all()                                   # populate disk
    assert _c("disk_writes") > 0
    assert kops.clear_compiled_cache() > 0      # drop in-memory state

    lev0, hits0 = _c("levelized"), _c("disk_hits")
    outs = run_all()
    assert _c("levelized") == lev0, "schedule came from levelize, not disk"
    assert _c("disk_hits") > hits0
    for i in range(0, len(outs), 2):
        assert np.array_equal(outs[i], x.astype(np.uint64) + y)
        assert np.array_equal(outs[i + 1], (fx * fy).astype(np.float16))


def test_install_writes_through_schedules_already_in_memory(tmp_path):
    """A cache installed after a program already ran (its schedule only in
    the in-memory LRU) still receives that schedule, so a process that
    drops its memory -- or a replica warming from the directory -- finds
    it on disk instead of levelizing again."""
    rng = np.random.default_rng(4)
    x = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    y = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    out = pim.sub(x, y, width=8)                 # no disk tier yet
    kops.set_artifact_cache(ArtifactCache(tmp_path / "late"))
    try:
        kops.clear_compiled_cache()
        lev0 = _c("levelized")
        assert np.array_equal(pim.sub(x, y, width=8), out)
        assert _c("levelized") == lev0
    finally:
        kops.set_artifact_cache(None)
        kops.clear_compiled_cache()


def test_aot_fallback_is_counted(cache, monkeypatch):
    """An AOT-tier failure falls back to plain jit -- still bit-exact --
    and counts ``pim.cache.aot_failed`` instead of passing silently."""
    def broken(*a, **k):
        raise RuntimeError("unreadable executable")

    monkeypatch.setattr(cache, "load_executable", broken)
    kops.clear_compiled_cache()       # no executable already in memory
    rng = np.random.default_rng(5)
    x = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    y = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    failed0 = _c("aot_failed")
    assert np.array_equal(pim.add(x, y, width=8), x.astype(np.uint64) + y)
    assert _c("aot_failed") > failed0


def test_corruption_recomputes_and_heals(cache):
    """A byte flipped anywhere in an artifact fails the checksum: the load
    counts ``disk_errors``, unlinks the bad file, recomputes the correct
    answer, and the write-through heals the entry for the next reader."""
    rng = np.random.default_rng(1)
    x = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    y = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    pim.mul(x, y, width=8)
    files = sorted(e.path for e in cache._files())
    assert files
    for path in files:
        with open(path, "r+b") as f:
            f.seek(20)
            b = f.read(1)
            f.seek(20)
            f.write(bytes([b[0] ^ 0xFF]))
    kops.clear_compiled_cache()

    err0 = _c("disk_errors")
    out = pim.mul(x, y, width=8)
    assert _c("disk_errors") > err0
    assert np.array_equal(out, x.astype(np.uint64) * y)
    for path in files:                  # bad files unlinked or rewritten
        if os.path.exists(path):
            with open(path, "rb") as f:
                assert f.read(8) == _MAGIC

    # healed: a third cold start loads from disk again
    kops.clear_compiled_cache()
    lev0 = _c("levelized")
    assert np.array_equal(pim.mul(x, y, width=8), out)
    assert _c("levelized") == lev0


def test_version_mismatch_is_plain_miss(cache):
    """A future-format magic makes every load a miss (never a parse):
    execution recomputes via levelize and overwrites the stale entry."""
    rng = np.random.default_rng(2)
    x = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    y = rng.integers(0, 1 << 8, 64).astype(np.uint8)
    out0 = pim.sub(x, y, width=8)
    for e in cache._files():
        with open(e.path, "r+b") as f:
            f.write(b"PIMART99")
    kops.clear_compiled_cache()
    lev0 = _c("levelized")
    out1 = pim.sub(x, y, width=8)
    assert _c("levelized") > lev0               # recomputed, no crash
    assert np.array_equal(out0, out1)


def test_size_cap_evicts_least_recently_used(tmp_path):
    """Writes past ``max_bytes`` evict oldest-mtime files first (loads
    refresh mtime, so the order is least-recently-used)."""
    from repro.core import pim_numerics
    prog = pim_numerics.program_for("int-serial", "add", 8)
    sched = kops.program_schedule(prog)
    big = ArtifactCache(tmp_path / "big")
    big.store_schedule(b"\x01" * 16, (6, 0, 0), "slots", sched)
    one = os.path.getsize(big._files()[0].path)

    c = ArtifactCache(tmp_path / "capped", max_bytes=int(one * 2.5))
    ev0 = _c("disk_evictions")
    paths = []
    for i, age in enumerate((100, 50)):
        key = bytes([i]) * 16
        c.store_schedule(key, (6, 0, 0), "slots", sched)
        p = c.sched_path(key, (6, 0, 0), "slots")
        t = os.path.getmtime(p) - age
        os.utime(p, (t, t))
        paths.append(p)
    c.store_schedule(b"\x10" * 16, (6, 0, 0), "slots", sched)
    assert not os.path.exists(paths[0]), "oldest entry survived the cap"
    assert os.path.exists(c.sched_path(b"\x10" * 16, (6, 0, 0), "slots"))
    assert _c("disk_evictions") > ev0
    assert c.total_bytes() <= c.max_bytes


def test_concurrent_multiprocess_writers(tmp_path):
    """Four processes race the same cache directory on the same programs:
    all succeed, and every surviving artifact is complete and loadable
    (atomic replace means no reader ever sees a torn file)."""
    cache_dir = tmp_path / "shared"
    script = (
        "import numpy as np\n"
        "from repro import pim_ufunc as pim\n"
        "pim.configure(cache_dir=%r)\n"
        "x = np.arange(64, dtype=np.uint8); y = (x * 3 + 1).astype(np.uint8)\n"
        "assert np.array_equal(pim.add(x, y, width=8),\n"
        "    x.astype(np.uint64) + y)\n"
        "assert np.array_equal(pim.mul(x, y, width=8),\n"
        "    x.astype(np.uint64) * y)\n"
        "print('OK')\n" % str(cache_dir))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = [subprocess.Popen([sys.executable, "-c", script], cwd=REPO,
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(4)]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0 and "OK" in out, err[-800:]
    c = ArtifactCache(cache_dir)
    headers = c.entries()               # _read verifies every checksum
    assert headers and all(h["kind"] in ("sched", "aot") for h in headers)


def test_warm_installs_schedules_and_executables(cache):
    """``warm()`` on a populated directory rebuilds programs from their
    recorded provenance and installs both tiers: the next call pays
    neither levelize nor XLA compile and stays bit-exact."""
    rng = np.random.default_rng(3)
    fx, fy = _fp16_operands(rng, 512)
    out0 = pim.fp_add(fx, fy)
    kops.clear_compiled_cache()

    counts = cache.warm()
    assert counts["schedules"] >= 1
    assert counts["executables"] >= 1
    lev0, miss0 = _c("levelized"), _c("disk_misses")
    out1 = pim.fp_add(fx, fy)
    assert _c("levelized") == lev0 and _c("disk_misses") == miss0
    assert np.array_equal(out0, out1)
    assert np.array_equal(out1, (fx + fy).astype(np.float16))


def _run_serve(reqs, cache_dir, metrics=None):
    args = [sys.executable, "-m", "repro.launch.serve", "--pim-serve",
            "--pim-window-ms", "20", "--pim-cache-dir", str(cache_dir)]
    if metrics is not None:
        args += ["--pim-metrics-file", str(metrics)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(args, input="\n".join(reqs) + "\n", cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    jlines = [json.loads(l) for l in proc.stderr.splitlines()
              if l.startswith("{")]
    (summary,) = [l for l in jlines if l["type"] == "summary"]
    warm = [l for l in jlines if l["type"] == "warm_start"]
    return summary, warm


def test_second_server_warm_starts_with_zero_recompiles(tmp_path):
    """The ISSUE 10 acceptance path end-to-end: two ``--pim-serve``
    replicas share one ``--pim-cache-dir``.  The first compiles and
    persists; the second warm-starts -- its summary shows **zero** fresh
    levelizations, nonzero disk hits, and the disk counters ride the
    Prometheus exposition."""
    reqs = [json.dumps({"op": op, "dtype": "uint8",
                        "x": [1, 2, 3], "y": [3, 2, 1]})
            for op in ("add", "mul", "sub") for _ in range(2)]
    cache_dir = tmp_path / "cache"
    metrics = tmp_path / "metrics.prom"

    s1, _ = _run_serve(reqs, cache_dir)
    assert s1["served"] == 6 and s1["errors"] == 0
    assert s1["cache"]["levelized"] > 0
    assert s1["cache"]["disk_writes"] > 0

    s2, warm = _run_serve(reqs, cache_dir, metrics=metrics)
    assert s2["served"] == 6 and s2["errors"] == 0
    (w,) = warm
    assert w["schedules"] >= 3 and w["executables"] >= 0
    assert s2["cache"]["levelized"] == 0, \
        "second replica recompiled despite a populated artifact cache"
    assert s2["cache"]["disk_hits"] > 0
    assert s2["cache"]["disk_errors"] == 0

    # counters materialize on first touch: the warm replica never
    # levelizes, so the disk-hit counter is the one that must be exposed
    text = metrics.read_text()
    for name in ("pim_cache_disk_hits", "pim_cache_hits"):
        assert name in text, f"{name} missing from Prometheus exposition"
