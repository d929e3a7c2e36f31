"""Op families by file.  The elementwise family makes, seed for seed, the
operands, calls, reference and control results and request lines that
the harness made before the families were split out of it; and a family
added as files alone (GEMV, in a bench root of the test's own) runs
through the harness, correct against its reference, and not correct
under its control or with its call broken."""

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench import control, harness, traffic
from bench.tests.test_bench_harness import EW, drive
from bench.tests.test_bench_spans import _with_a_chip
from repro import pim_ufunc

SEEDS = (2**31 + 3, 2**40 + 17)
#: Rows of the committed configurations in the digests.
ROWS = 1000
#: "<cell>/<seed>" -> digests of the operand pool, of two blocks' calls
#: with their operands, and of every shape's reference and control
#: results on every pool set; "serve-open/<seed>" -> of the pool and the
#: request lines (the mix at 64 and 256 rows).  Taken with the harness
#: as it was before the families were split out of it.
DIGESTS = {
    "fp32-fig9-64Mi/2147483651": [
        "a9de1a617d06e844", "4970e85e5f98d876", "9f623cafcbb0c8db"],
    "fp32-fig9-64Mi/1099511627793": [
        "109e81909cef3c47", "bbe2b27bff652a05", "7c72143a71fcb3a0"],
    "int32-fig9-4Mi/2147483651": [
        "d114b45a936de2b6", "dde4a56f9754b1e0", "1308a57f6c1aecb1"],
    "int32-fig9-4Mi/1099511627793": [
        "156578ca18a2257e", "f4a5943d26af9984", "89ad80c4b9fe2a71"],
    "fp32-fig9-64Mi-x4/2147483651": [
        "a9de1a617d06e844", "4970e85e5f98d876", "9f623cafcbb0c8db"],
    "fp32-fig9-64Mi-x4/1099511627793": [
        "109e81909cef3c47", "bbe2b27bff652a05", "7c72143a71fcb3a0"],
    "serve-open/2147483651": ["1752c7f39919f4e3", "4059e251061fff8c"],
    "serve-open/1099511627793": ["79e980d020088846", "1f71f2f9882916a4"],
}


def _flat(p):
    if isinstance(p, (tuple, list)):
        for q in p:
            yield from _flat(q)
    else:
        yield p


def digest(*parts) -> str:
    h = hashlib.sha256()
    for a in _flat(parts):
        if isinstance(a, np.ndarray):
            h.update(f"{a.dtype.str}{a.shape}".encode())
            h.update(np.ascontiguousarray(a).tobytes())
        else:
            h.update(repr(a).encode())
    return h.hexdigest()[:16]


def _made(key: str):
    name, seed = key.split("/")
    seed = int(seed)
    if name == "serve-open":
        config = harness._read_json(os.path.join(harness.BENCH, "configs",
                                                 "fig9-fp32.json"))
        mix = dict(harness._read_json(os.path.join(
            harness.BENCH, "traffic", "serve-open.json")),
            row_counts={"64": 2, "256": 1})
        tr = traffic.make(mix, config, seed, EW)
        served = harness.Served(config, EW, serve=lambda inp, outp: None)
        served.start(tr)
        served.close()
        return [digest(tr.pool), digest(sorted(served.lines.items()))]
    cell = harness.load_cell(name)
    assert cell.family.__file__ == EW.__file__
    tr = traffic.make(cell.mix, dict(cell.config, rows=ROWS), seed,
                      cell.family)
    sends = tr.block * 2
    return [digest(tr.pool),
            digest([(s.op, s.rows, s.gap_s) for s in tr.block],
                   [tr.pair(i) for i in range(len(sends))],
                   [tr.operands(i, s) for i, s in enumerate(sends)]),
            digest([(EW.reference(op, *tr.head(k, rows)),
                     EW.control(op, *tr.head(k, rows)))
                    for op, rows in tr.shapes
                    for k in range(len(tr.pool))])]


@pytest.mark.parametrize("key", sorted(DIGESTS))
def test_the_elementwise_family_makes_what_the_harness_made(key):
    assert _made(key) == DIGESTS[key]


# --------------------------------------------------------------------------
# a family added as files alone
# --------------------------------------------------------------------------

GEMV_CONFIG = {
    "name": "gemv-tiny", "family": "gemv",
    "source": "https://arxiv.org/abs/2105.03814",
    "ops": ["gemv"], "dtype": "uint32", "algorithm": "bit-serial",
    "k": 8, "rows": 40, "operands": {"a_min": 0, "x_min": 0}}
MIXES = {
    "gemv-closed": {"arrivals": "closed", "order": "rotation", "pool": 2,
                    "row_shards": 1},
    "gemv-served": {"path": "serve", "arrivals": "poisson",
                    "rate_per_s": 10.0, "row_shards": 1}}
CLOSED, SERVED = (f"gemv-tiny.{m}" for m in MIXES)


def _tree(path: str) -> dict:
    """Every file under ``path`` but compiled ones -> its sha256."""
    out = {}
    for d, dirs, files in os.walk(path):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), path)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def gemv_root(tmp_path_factory):
    """A bench root holding ``BENCHMARK.json`` with a GEMV configuration
    and two cells added, and the family, configuration and mix files
    they name: new files and entries only."""
    root = tmp_path_factory.mktemp("bench-root")
    spec = harness._read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "gemv-tiny", "source": GEMV_CONFIG["source"],
        "file": "bench/configs/gemv-tiny.json", "reduced": ["rows"],
        "why": "PrIM GEMV, uint32, at M 5 and K 8"})
    spec["workloads"] += [
        {"name": f"gemv-tiny.{m}", "config": "gemv-tiny", "traffic": m,
         "chips": 1, "why": "a tiny GEMV"} for m in MIXES]
    files = {"BENCHMARK.json": spec,
             "bench/configs/gemv-tiny.json": GEMV_CONFIG,
             **{f"bench/traffic/{m}.json": v for m, v in MIXES.items()}}
    for rel, obj in files.items():
        os.makedirs(os.path.dirname(root / rel), exist_ok=True)
        with open(root / rel, "w") as f:
            json.dump(obj, f)
    os.makedirs(root / "bench" / "families")
    shutil.copy(os.path.join(os.path.dirname(__file__), "gemv_family.py"),
                root / "bench" / "families" / "gemv.py")
    return str(root)


def test_the_gemv_reference_is_exact(gemv_root):
    """Against Python ints at the published K, where the sums pass 64
    bits; the control differs in every output."""
    fam = harness.load_family("gemv", gemv_root)
    rng = traffic.rng_for(2**35 + 1)
    a = traffic.int_operands(rng, "uint32", 3 * 4096, 0).reshape(3, 4096)
    x = traffic.int_operands(rng, "uint32", 4096, 0)
    want = a.astype(object) @ x.astype(object)
    assert min(want) >= 2**64
    assert list(fam.reference("gemv", a, x)) == list(want)
    assert fam.mismatched("gemv", fam.control("gemv", a, x), want,
                          a.size) == a.size


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
def test_a_family_added_as_files_runs_correct(gemv_root, traced,
                                              monkeypatch):
    before = _tree(harness.BENCH)
    cell = harness.load_cell(CLOSED, gemv_root)
    assert cell.family.__file__ == os.path.join(gemv_root, "bench",
                                                "families", "gemv.py")
    if traced:
        _with_a_chip(monkeypatch)
    line, lines = drive(cell, harness.make_system(cell), traced=traced)
    assert line["correct"], line["checks"]
    assert line["checks"] == {"mismatch_rows.gemv": {"value": 0,
                                                     "limit": 0}}
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert lines[0]["window"]["compiles"] == 0     # warmed in set-up
    if traced:
        metrics = line["metrics"]
        assert "frontend_pct" not in metrics       # no prepare span
        assert 0 < metrics["pim_exec_roofline"]["value"] < 100
        assert metrics["gate_rows_per_dev_s"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"rows_per_s", "call_p95_s",
                                        "setup_s"}
    assert _tree(harness.BENCH) == before


def _altered(monkeypatch):
    orig = pim_ufunc.gemv

    def altered(a, x, **kw):
        out = np.array(orig(a, x, **kw), copy=True)
        out[len(out) // 2] ^= 1
        return out
    monkeypatch.setattr(pim_ufunc, "gemv", altered)


def _left_out(monkeypatch):
    orig = pim_ufunc.gemv
    monkeypatch.setattr(pim_ufunc, "gemv",
                        lambda a, x, **kw: orig(a, x, **kw)[:-1])


def _raises(monkeypatch):
    """Every call after the first, which is the warm-up's, raises."""
    orig, calls = pim_ufunc.gemv, []

    def raises(a, x, **kw):
        calls.append(1)
        if len(calls) > 1:
            raise RuntimeError("planted")
        return orig(a, x, **kw)
    monkeypatch.setattr(pim_ufunc, "gemv", raises)


@pytest.mark.parametrize("fault", [_altered, _left_out, _raises, None],
                         ids=["output_altered", "output_left_out",
                              "call_raises", "control"])
def test_a_broken_gemv_is_not_correct(gemv_root, monkeypatch, fault):
    cell = harness.load_cell(CLOSED, gemv_root)
    if fault is None:
        system = control.control_system(harness, cell)
    else:
        fault(monkeypatch)
        system = harness.make_system(cell)
    line, _ = drive(cell, system)
    assert not line["correct"]
    assert line["failed"] > 0
    assert line["checks"]["mismatch_rows.gemv"]["value"] > 0


def test_a_family_with_no_wire_form_refuses_the_serve_path(gemv_root):
    with pytest.raises(harness.BenchError,
                       match="'gemv' family has no wire form"):
        harness.load_cell(SERVED, gemv_root)
