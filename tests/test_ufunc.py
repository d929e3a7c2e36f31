"""Ufunc frontend + streaming/sharded executor: dispatch, validation, the
1M-row chunked path vs the cycle-accurate oracle, LRU cache eviction, and
the executor shape guards."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro import pim_ufunc as pim
from repro.core import bitserial as bs
from repro.core.floatfmt import BF16, FORMATS
from repro.kernels import ops as kops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- int ufuncs

@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.uint32])
def test_int_ufuncs_match_numpy(dtype):
    rng = np.random.default_rng(int(np.dtype(dtype).itemsize))
    hi = 1 << (np.dtype(dtype).itemsize * 8)
    x = rng.integers(0, hi, 200).astype(dtype)
    y = rng.integers(0, hi, 200).astype(dtype)
    d = rng.integers(1, hi, 200).astype(dtype)
    w = np.dtype(dtype).itemsize * 8
    assert np.array_equal(pim.add(x, y), x.astype(np.uint64) + y)
    assert np.array_equal(
        pim.sub(x, y),
        ((x.astype(np.int64) - y) % hi).astype(np.uint64))
    assert np.array_equal(pim.mul(x, y), x.astype(np.uint64) * y)
    q, r = pim.div(x, d)
    assert np.array_equal(q, x.astype(np.uint64) // d)
    assert np.array_equal(r, x.astype(np.uint64) % d)
    assert w  # width inferred, no exception


def test_int_ufunc_broadcast_and_shape():
    rng = np.random.default_rng(3)
    m = rng.integers(0, 256, (6, 5)).astype(np.uint8)
    out = pim.add(m, np.uint8(7))
    assert out.shape == (6, 5)
    assert np.array_equal(out, m.astype(np.uint64) + 7)


def test_int_ufunc_explicit_width_object_dtype():
    """width > 64: arbitrary-precision object arrays in and out."""
    x = np.array([(1 << 70) + 3, 5, 0], object)
    y = np.array([(1 << 70) + 1, 2, 0], object)
    out = pim.add(x, y, width=71)
    assert out.dtype == object
    assert [int(v) for v in out] == [int(a) + int(b) for a, b in zip(x, y)]


def test_int_ufunc_validation():
    u8 = np.arange(4, dtype=np.uint8)
    with pytest.raises(TypeError):
        pim.add(np.arange(4, dtype=np.int32), np.arange(4, dtype=np.int32))
    with pytest.raises(TypeError):
        pim.add(u8, np.arange(4, dtype=np.uint16))   # mixed widths
    with pytest.raises(ValueError):
        pim.add(np.array([300], object), np.array([1], object), width=8)
    with pytest.raises(ValueError):
        pim.div(u8, np.zeros(4, np.uint8))
    with pytest.raises(TypeError):
        pim.add(u8, u8, not_an_option=1)
    with pytest.raises(ValueError):
        pim.add(u8, u8, backend="verilog")


# ---------------------------------------------------------------- fp ufuncs

def test_fp_ufuncs_match_numpy():
    rng = np.random.default_rng(0)
    for dtype in (np.float16, np.float32):
        a = rng.standard_normal(128).astype(dtype)
        b = (rng.standard_normal(128).astype(dtype) +
             np.asarray(2.0, dtype) * np.sign(rng.standard_normal(128))
             .astype(dtype))
        b = np.where(b == 0, np.asarray(1.0, dtype), b)
        assert np.array_equal(pim.fp_add(a, b), (a + b).astype(dtype))
        assert np.array_equal(pim.fp_sub(a, b), (a - b).astype(dtype))
        assert np.array_equal(pim.fp_mul(a, b), (a * b).astype(dtype))
        assert np.array_equal(pim.fp_div(a, b), (a / b).astype(dtype))


def test_fp_ufunc_bf16_bits_vs_oracle():
    rng = np.random.default_rng(1)
    xb = BF16.random_bits(rng, 80, emin=120, emax=134).astype(np.uint64)
    yb = BF16.random_bits(rng, 80, emin=120, emax=134).astype(np.uint64)
    for op in ("add", "mul"):
        got = getattr(pim, f"fp_{op}")(xb, yb, fmt="bf16")
        want = [BF16.op_exact(op, int(a), int(b)) for a, b in zip(xb, yb)]
        assert [int(v) for v in got] == want, op


def test_fp_ufunc_validation():
    f = np.ones(4, np.float32)
    with pytest.raises(ValueError):
        pim.fp_add(np.array([np.nan], np.float32), f[:1])
    with pytest.raises(ValueError):
        pim.fp_add(f[:1], np.array([np.inf], np.float32))
    with pytest.raises(ValueError):            # subnormal
        pim.fp_mul(np.array([1e-42], np.float32), f[:1])
    with pytest.raises(ValueError):
        pim.fp_div(f, np.zeros(4, np.float32))
    with pytest.raises(TypeError):
        pim.fp_add(f, np.ones(4, np.float16))  # mixed dtypes
    with pytest.raises(ValueError):
        pim.fp_add(np.array([1], np.uint64), np.array([1], np.uint64),
                   fmt="fp128")
    # check=False skips the operand scan (results then undefined, but the
    # call must go through the executor unimpeded)
    out = pim.fp_add(f, f, check=False)
    assert out.shape == (4,)


def _exact_fp_check(op, name, bits, fmt, reject_zero=False):
    """The operand check as it was before its chunked pass: uint64 (or
    object) exponent and mantissa arrays, each encoding class in turn.
    Kept as the oracle of the check's messages and their precedence."""
    b = bits if bits.dtype == object else bits.astype(np.uint64)
    e = np.array([(int(v) >> fmt.nm) & ((1 << fmt.ne) - 1) for v in b.flat],
                 np.int64) if b.dtype == object else \
        ((b >> np.uint64(fmt.nm)) & np.uint64((1 << fmt.ne) - 1)
         ).astype(np.int64)
    m = np.array([int(v) & ((1 << fmt.nm) - 1) for v in b.flat], np.int64) \
        if b.dtype == object else \
        (b & np.uint64((1 << fmt.nm) - 1)).astype(np.int64)
    emax = (1 << fmt.ne) - 1
    if (e == emax).any():
        raise ValueError(f"pim.{op}: operand {name} contains NaN/Inf "
                         "(excluded by the PIM suite)")
    if ((e == 0) & (m != 0)).any():
        raise ValueError(f"pim.{op}: operand {name} contains subnormals "
                         "(excluded by the PIM suite)")
    if reject_zero and ((e == 0) & (m == 0)).any():
        raise ValueError(f"pim.{op}: zero divisor")


def _patterns(f):
    """Named bit patterns of format ``f`` at the edges of each class."""
    emax, sign = (1 << f.ne) - 1, 1 << (f.nbits - 1)
    inf = emax << f.nm
    return {"+inf": inf, "-inf": sign | inf,
            "qnan": inf | (1 << (f.nm - 1)), "snan": inf | 1,
            "min_sub": 1, "max_sub": (1 << f.nm) - 1,
            "+0": 0, "-0": sign,
            "min_normal": 1 << f.nm,
            "max_normal": ((emax - 1) << f.nm) | ((1 << f.nm) - 1)}


# (label, format, container dtype, native float dtype or None for fmt=)
_CHECK_FORMATS = [("fp16", "fp16", np.uint16, np.float16),
                  ("fp32", "fp32", np.uint32, np.float32),
                  ("bf16-u16", "bf16", np.uint16, None),
                  ("bf16-u64", "bf16", np.uint64, None),
                  ("bf16-i64", "bf16", np.int64, None),
                  ("bf16-u16be", "bf16", np.dtype(">u2"), None)]
_ROWS = pim._CHECK_CHUNK + 17        # a second, ragged chunk


def _check_cases():
    for label, fmt, cont, native in _CHECK_FORMATS:
        for pat in _patterns(FORMATS[fmt]):
            for role in ("x", "y"):
                for row in (0, pim._CHECK_CHUNK + 3):
                    yield pytest.param(fmt, cont, native,
                                       {role: [(row, pat)]},
                                       id=f"{label}-{pat}-{role}@{row}")
        # NaN late, subnormal early in x: the NaN/Inf message still wins
        yield pytest.param(fmt, cont, native,
                           {"x": [(2, "min_sub"), (_ROWS - 1, "qnan")]},
                           id=f"{label}-precedence")
        # a zero dividend is a valid operand of fp_div
        yield pytest.param(fmt, cont, native, {"x": [(5, "+0")]},
                           id=f"{label}-zero-dividend")


@pytest.mark.parametrize("fmt,cont,native,planted", list(_check_cases()))
def test_fp_check_matches_exact_classification(fmt, cont, native, planted):
    """The chunked check raises exactly what the exact classification
    raises (type, message, precedence: x before y, NaN/Inf before
    subnormal before zero divisor), and nothing on a clean operand."""
    f = FORMATS[fmt]
    pats = _patterns(f)
    ops = {n: np.full(_ROWS, pats["min_normal"] | (3 << (f.nm - 2)), cont)
           for n in ("x", "y")}
    for n, places in planted.items():
        for row, pat in places:
            ops[n][row] = pats[pat]

    def outcome(fn):
        try:
            fn()
        except (ValueError, TypeError) as e:
            return type(e), str(e)
        return None

    def exact():
        _exact_fp_check("fp_div", "x", ops["x"], f)
        _exact_fp_check("fp_div", "y", ops["y"], f, reject_zero=True)

    if native is None:
        args, kw = (ops["x"], ops["y"]), {"fmt": fmt}
    else:
        args, kw = (ops["x"].view(native), ops["y"].view(native)), {}
    want = outcome(exact)
    assert outcome(lambda: pim.prepare("fp_div", *args, **kw)) == want
    clean = all(pat in ("+0", "-0", "min_normal", "max_normal")
                for row, pat in planted.get("x", ())) and \
        all(pat in ("min_normal", "max_normal")
            for row, pat in planted.get("y", ()))
    assert (want is None) == clean


@pytest.mark.parametrize("dtype", [np.float16, np.float32])
def test_fp_prepare_hands_on_views(dtype):
    """A contiguous native operand reaches the dispatcher as a view of its
    bits in its own width: no cast, no copy."""
    x = np.linspace(1, 2, 257, dtype=dtype)
    y = np.linspace(3, 4, 257, dtype=dtype)
    prep = pim.prepare("fp_mul", x, y)
    for n, v in (("x", x), ("y", y)):
        assert prep.inputs[n].dtype == pim._FMT_VIEW[pim._NP_FMT[x.dtype]]
        assert np.shares_memory(prep.inputs[n], v)


@pytest.mark.parametrize("dtype,op,kw", [
    (np.float16, "fp_add", {}), (np.float32, "fp_mul", {}),
    (np.float16, "fp_sub", {"parallel": True}),
    (np.float32, "fp_sub", {"parallel": True})])
def test_fp_prepare_run_bit_identical(dtype, op, kw):
    """prepare(...).run() on the narrow views equals numpy bit for bit,
    bit-parallel sub (y's sign flipped in the view's own dtype) too; the
    caller's y is left as it was."""
    rng = np.random.default_rng(7)
    x = (rng.standard_normal(96) + 3).astype(dtype)
    y = (rng.standard_normal(96) * 2).astype(dtype)
    y = np.where(y == 0, np.asarray(1, dtype), y)
    y0 = y.copy()
    got = pim.prepare(op, x, y, **kw).run()
    want = {"fp_add": x + y, "fp_sub": x - y, "fp_mul": x * y}[op]
    assert got.dtype == dtype
    assert np.array_equal(got.view(f"u{x.itemsize}"),
                          want.astype(dtype).view(f"u{x.itemsize}"))
    assert np.array_equal(y, y0)


def test_fp_check_counters():
    """``pim.prepare.check_rows`` counts the rows the chunked pass cleared
    (both operands of a clean call); ``check_slow`` counts operands the
    exact classification read: object arrays, and a call that raises."""
    from repro.runtime.telemetry import REGISTRY
    g = REGISTRY.group("pim.prepare")
    rows0, slow0 = g["check_rows"], g["check_slow"]
    x = np.linspace(1, 2, 1000, dtype=np.float32)
    pim.prepare("fp_add", x, x)
    assert g["check_rows"] - rows0 == 2 * x.size
    assert g["check_slow"] == slow0
    bits = np.array([int(v) for v in x.view(np.uint32) >> 16], object)
    pim.prepare("fp_add", bits, bits, fmt="bf16")
    assert g["check_slow"] == slow0 + 2
    with pytest.raises(ValueError, match="zero divisor"):
        pim.prepare("fp_div", x, np.zeros_like(x))
    assert g["check_slow"] == slow0 + 3
    assert g["check_rows"] - rows0 == 3 * x.size


# ----------------------------------------------- streaming + sharded 1M row

def test_stream_1m_rows_bit_exact_vs_oracle():
    """Acceptance: pim.add on >= 1M rows via the chunked path, bit-exact
    against the cycle-accurate numpy oracle on sampled rows (and against
    numpy's own arithmetic on every row)."""
    rng = np.random.default_rng(7)
    n = (1 << 20) + 17                        # ragged last chunk
    x = rng.integers(0, 1 << 32, n).astype(np.uint32)
    y = rng.integers(0, 1 << 32, n).astype(np.uint32)
    out = pim.add(x, y)                       # routes through streaming
    assert np.array_equal(out, x.astype(np.uint64) + y)
    idx = rng.integers(0, n, 64)
    oracle = pim.add(x[idx], y[idx], backend="numpy")
    assert np.array_equal(out[idx], oracle)


def test_stream_1m_rows_fp16_sampled_vs_oracle():
    rng = np.random.default_rng(8)
    n = 1 << 20
    xb = FORMATS["fp16"].random_bits(rng, n, emin=10, emax=20)
    yb = FORMATS["fp16"].random_bits(rng, n, emin=10, emax=20)
    x = xb.astype(np.uint16).view(np.float16)
    y = yb.astype(np.uint16).view(np.float16)
    out = pim.fp_add(x, y)
    idx = rng.integers(0, n, 48)
    oracle = pim.fp_add(x[idx], y[idx], backend="numpy")
    assert np.array_equal(out[idx], oracle)
    assert np.array_equal(out[idx], (x[idx] + y[idx]).astype(np.float16))


def test_streaming_matches_monolithic_across_chunk_edges():
    """Chunk boundaries at n_rows {0, 1, 31, 32, 33} offsets from the edge
    must be invisible: streaming == one-shot run_program."""
    p = bs.build_add(16)
    rng = np.random.default_rng(9)
    for n in (96, 97, 127, 128, 129):
        x = rng.integers(0, 1 << 16, n).astype(np.uint64)
        y = rng.integers(0, 1 << 16, n).astype(np.uint64)
        one = kops.run_program(p, {"x": x, "y": y}, n, backend="ref")
        stream = kops.run_program_streaming(p, {"x": x, "y": y}, n,
                                            backend="ref", chunk_rows=32)
        assert set(one) == set(stream)
        for k in one:
            assert np.array_equal(one[k], stream[k]), (n, k)


def test_streaming_rejects_bad_inputs():
    p = bs.build_add(8)
    x = np.arange(64, dtype=np.uint64)
    with pytest.raises(ValueError):
        kops.run_program_streaming(p, {"x": x, "y": x}, 64, backend="numpy")
    with pytest.raises(ValueError):
        kops.run_program_streaming(p, {"x": x[:10], "y": x[:10]}, 64,
                                   backend="ref", chunk_rows=32)


def test_sharded_parity_subprocess():
    """Real multi-device sharding (forced 4-device CPU child): streamed +
    sharded results must be bit-exact vs host arithmetic on both executor
    families (fused <= 32-cell ports and padded-io wide ports)."""
    code = """
import numpy as np
from repro.core import bitserial as bs
from repro.kernels import ops as kops
import jax
assert len(jax.devices()) == 4, jax.devices()
mesh = kops.row_mesh()
assert mesh is not None and mesh.devices.size == 4
rng = np.random.default_rng(0)
n = 100_001
x = rng.integers(0, 1 << 32, n).astype(np.uint64)
y = rng.integers(0, 1 << 32, n).astype(np.uint64)
for backend in ("ref", "pallas"):
    out = kops.run_program_streaming(bs.build_add(32), {"x": x, "y": y}, n,
                                     backend=backend, chunk_rows=32768,
                                     mesh=mesh)["z"]
    assert np.array_equal(out, x + y), backend
pm = bs.build_mul(48)             # 96-cell z port -> padded-io + object out
xm = x[:3000] & ((1 << 48) - 1)
ym = y[:3000] & ((1 << 48) - 1)
zm = kops.run_program_streaming(pm, {"x": xm, "y": ym}, 3000, backend="ref",
                                chunk_rows=1024, mesh=mesh)["z"]
assert all(int(g) == int(a) * int(b) for g, a, b in zip(zm, xm, ym))
print("SHARDED-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDED-OK" in proc.stdout


# ------------------------------------------------------- LRU compiled cache

def _mini_program(seed, n_gates=12):
    from repro.core.gates import Builder

    rng = np.random.default_rng(seed)
    b = Builder()
    avail = b.input("x", 16) + b.input("y", 16)
    fns = [b.nor, b.or_, b.and_, b.xor, b.xnor, b.nand]
    for _ in range(n_gates):
        f = fns[rng.integers(0, len(fns))]
        i, j = rng.integers(0, len(avail), 2)
        avail.append(f(avail[i], avail[j]))
    b.output("z", avail[-16:])
    return b.finish()


def test_compiled_cache_lru_eviction_bit_exact():
    """The compiled-program cache must stay bounded, and eviction must be
    invisible to results (recompilation is pure)."""
    old_cap = kops.set_compiled_cache_cap(2)
    try:
        progs = [_mini_program(100 + s) for s in range(5)]
        rng = np.random.default_rng(0)
        ins = {"x": rng.integers(0, 1 << 16, 33).astype(np.uint64),
               "y": rng.integers(0, 1 << 16, 33).astype(np.uint64)}
        want = [kops.run_program(p, ins, 33, backend="numpy")["z"]
                for p in progs]
        for _ in range(2):                    # second pass hits evictions
            for p, w in zip(progs, want):
                got = kops.run_program(p, ins, 33, backend="ref")["z"]
                assert np.array_equal(got, w)
                assert len(kops._compiled) <= 2
    finally:
        kops.set_compiled_cache_cap(old_cap)
    with pytest.raises(ValueError):
        kops.set_compiled_cache_cap(0)


# ------------------------------------------------------ executor shape guard

def test_executor_shape_checks_raise_value_error():
    """Shape guards must be explicit raises (assert dies under python -O)."""
    import jax.numpy as jnp
    from repro.kernels import pim_exec

    ops = jnp.zeros(1, jnp.int32)
    good = jnp.zeros((4, pim_exec.TILE_W), jnp.uint32)
    with pytest.raises(ValueError, match="n_cells"):
        pim_exec.pim_exec_padded(good, ops, ops, ops, ops, n_cells=5)
    with pytest.raises(ValueError, match="TILE_W"):
        pim_exec.pim_exec_padded(
            jnp.zeros((4, pim_exec.TILE_W + 1), jnp.uint32),
            ops, ops, ops, ops, n_cells=4)
    la = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="n_cells"):
        pim_exec.pim_exec_level_padded(good, la, la, la, n_cells=3)
    with pytest.raises(ValueError, match="TILE_W"):
        pim_exec.pim_exec_level_padded(
            jnp.zeros((4, 8), jnp.uint32), la, la, la, n_cells=4)


# -------------------------------------------------------------- serving API

def test_serve_pim_request_roundtrip():
    from repro.launch import serve

    r = serve.pim_request({"op": "add", "dtype": "uint16",
                           "x": [3, 5], "y": [4, 6]})
    assert r["result"] == [7, 11] and r["rows"] == 2
    r = serve.pim_request({"op": "div", "dtype": "uint8",
                           "x": [17], "y": [5]})
    assert (r["q"], r["r"]) == ([3], [2])
    r = serve.pim_request({"op": "fp_add", "fmt": "bf16",
                           "x": [16256], "y": [16256]})
    assert r["result"] == [16384]             # 1.0 + 1.0 == 2.0
    r = serve.pim_request({"op": "nope", "x": [], "y": []})
    assert "error" in r
    r = serve.pim_request({"op": "div", "dtype": "uint8",
                           "x": [1], "y": [0]})
    assert r["error"]["code"] == "bad_request"
    assert "zero divisor" in r["error"]["message"]


def test_serve_pim_stdin_loop():
    import io
    import json

    from repro.launch import serve

    inp = io.StringIO('{"op":"add","dtype":"uint8","x":[1],"y":[2]}\n'
                      '\nnot json\n')
    outp = io.StringIO()
    served = serve.serve_pim_stdin(inp, outp)
    lines = [json.loads(l) for l in outp.getvalue().splitlines()]
    assert served == 2
    assert lines[0]["result"] == [3]
    assert "error" in lines[1]
