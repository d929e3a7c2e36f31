#!/usr/bin/env python3
"""Chip smoke test: the PIM arithmetic path, once, on a TPU, at full size.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # only the row-sharded phase

Runs in this one process through the entry points a user calls --
``repro.pim_ufunc`` (``pim.prepare(...).run()``, the ufuncs), the batched
server behind ``--pim-serve`` (``launch.serve.serve_pim_batched``) and
``kernels.ops.run_program`` for the gate-serial executor -- and checks every
result bit-exactly against host numpy arithmetic.  Phases on one chip:

* ``full_memory``: fp16 add over 64 Mi rows as one dispatch on the default
  plan (ref backend, slots schedule, rows32): the paper's 8 GB memristive
  memory in lockstep (``core/device_model.py``).
* ``family``: uint32 add/sub/mul/div and fp32 add/sub/mul/div, 4 Mi rows
  each, default chunking.
* ``serve``: 32 JSON requests (uint16 and fp16 add/sub/mul/div, 4 each,
  64 Ki rows each); no response may carry ``error``/``degraded``/``shed``.
* ``mosaic``: the two Pallas kernels that lower on a TPU -- the static-slice
  levelized kernel (fp16 add, ``schedule="slots-static"``) and the
  gate-serial kernel (uint16 add, ``levelized=False``) -- run compiled and
  show a ``tpu_custom_call`` in their compiled text.

``--four-chips`` runs only fp16 add over 64 Mi rows with ``shards=4`` on a
four-chip host, against the same rows at ``shards=1`` on one of its chips.

Every timing printed is a smoke figure from one or two calls, not a
benchmark number.  The last stdout line is ``{"ok": true, "device":
{...}}``; any failure exits nonzero without it.  With no TPU the script
fails at once: it never falls back to the CPU.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

FULL_ROWS = 1 << 26          # 8 GB of memristive memory, 64 Mi rows
FAMILY_ROWS = 1 << 22
SERVE_ROWS = 1 << 16
SERVE_REQUESTS_PER_OP = 4
MOSAIC_ROWS = 1 << 20

_NP_FLOAT = {"fp16": (np.float16, np.uint16), "fp32": (np.float32, np.uint32)}
_INT_REF = {
    "add": lambda x, y: x.astype(np.uint64) + y,
    "sub": lambda x, y: (x - y).astype(np.uint64),     # wraps mod 2**width
    "mul": lambda x, y: x.astype(np.uint64) * y.astype(np.uint64),
    "div": lambda x, y: ((x // y).astype(np.uint64),
                         (x % y).astype(np.uint64)),
}
_FP_REF = {"fp_add": np.add, "fp_sub": np.subtract,
           "fp_mul": np.multiply, "fp_div": np.divide}


def log(**fields) -> None:
    print(json.dumps(fields, sort_keys=True), flush=True)


def require_tpu():
    """The JAX module and its devices; exits at once without a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend "
                 f"{devices[0].platform!r}); this script runs only on the "
                 f"chip")
    return jax, devices


def fp_operands(rng, fmt_name: str, n: int):
    """Random normal-range floats of one format.  Exponents are mid-range
    (as in benchmarks/run.py), from bias-4 up, so that no sum, difference,
    product or quotient leaves the normal range the paper covers."""
    from repro.core.floatfmt import FORMATS
    fmt = FORMATS[fmt_name]
    ftype, utype = _NP_FLOAT[fmt_name]
    bits = fmt.random_bits(rng, n, emin=fmt.bias - 4, emax=fmt.bias + 5)
    return bits.astype(utype).view(ftype)


def int_operands(rng, dtype, n: int, divisor: bool = False):
    hi = 1 << (8 * np.dtype(dtype).itemsize)
    return rng.integers(1 if divisor else 0, hi, n, dtype=np.uint64
                        ).astype(dtype)


def reference(op: str, x, y):
    """Host numpy result: uint64 values for fixed point (``(q, r)`` for
    div), the unsigned bit pattern for floats (IEEE RNE in the operands'
    own dtype; exact for these formats)."""
    if op in _FP_REF:
        out = _FP_REF[op](x, y)
        return out.view(_NP_FLOAT["fp16" if x.dtype == np.float16
                                  else "fp32"][1])
    return _INT_REF[op](x, y)


def check(what: str, got, want) -> None:
    if isinstance(want, tuple):
        for g, w, part in zip(got, want, ("q", "r")):
            check(f"{what} {part}", g, w)
        return
    got = np.asarray(got)
    if got.dtype.kind == "f":
        got = got.view(want.dtype)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.count_nonzero(got != want)) \
            if got.shape == want.shape else -1
        raise AssertionError(f"{what}: {bad} of {want.size} rows differ "
                             f"from host numpy")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run_twice(what: str, prep, want, rows: int):
    """Run a prepared request twice (the first call compiles), check both
    results; returns the smoke timings and the second result."""
    got, first = timed(prep.run)
    check(what, got, want)
    got, second = timed(prep.run)
    check(what, got, want)
    return {"rows": rows, "first_call_s": first, "second_call_s": second,
            "rows_per_s": rows / second,
            "compile_s_est": first - second}, got


def require_mosaic(kernel: str, compiled_text: str) -> None:
    """The kernel ran compiled through Mosaic, not in interpret mode."""
    from repro.kernels import pim_exec
    if pim_exec.interpret_mode():
        raise AssertionError(f"{kernel}: Pallas runs in interpret mode")
    if "tpu_custom_call" not in compiled_text:
        raise AssertionError(f"{kernel}: no tpu_custom_call in its "
                             f"compiled text")


def phase_full_memory(jax, pim, rng) -> None:
    x = fp_operands(rng, "fp16", FULL_ROWS)
    y = fp_operands(rng, "fp16", FULL_ROWS)
    want = reference("fp_add", x, y)
    prep = pim.prepare("fp_add", x, y, chunk_rows=FULL_ROWS)
    plan = prep.plan
    if (plan.backend.name, plan.schedule, plan.layout.name, plan.mesh) != \
            ("ref", "slots", "rows32", None):
        raise AssertionError(f"full_memory: not the default plan: {plan}")
    stats, _ = run_twice("fp16 add, 64 Mi rows", prep, want, FULL_ROWS)
    mem = jax.devices()[0].memory_stats() or {}
    log(phase="full_memory", op="fp_add", fmt="fp16", dispatches=1,
        peak_bytes_in_use=mem.get("peak_bytes_in_use"),
        bytes_limit=mem.get("bytes_limit"), **stats)


def phase_families(pim, rng) -> None:
    for op in ("add", "sub", "mul", "div"):
        x = int_operands(rng, np.uint32, FAMILY_ROWS)
        y = int_operands(rng, np.uint32, FAMILY_ROWS, divisor=op == "div")
        stats, _ = run_twice(f"uint32 {op}", pim.prepare(op, x, y),
                             reference(op, x, y), FAMILY_ROWS)
        log(phase="family", op=op, dtype="uint32", **stats)
    for op in _FP_REF:
        x = fp_operands(rng, "fp32", FAMILY_ROWS)
        y = fp_operands(rng, "fp32", FAMILY_ROWS)
        stats, _ = run_twice(f"fp32 {op}", pim.prepare(op, x, y),
                             reference(op, x, y), FAMILY_ROWS)
        log(phase="family", op=op, dtype="float32", **stats)


def phase_serve(rng) -> None:
    from repro.launch.serve import serve_pim_batched
    lines, wants = [], []
    for dtype, ops in ((np.uint16, tuple(_INT_REF)),
                       (np.float16, tuple(_FP_REF))):
        for op in ops:
            for _ in range(SERVE_REQUESTS_PER_OP):
                if dtype == np.float16:
                    x = fp_operands(rng, "fp16", SERVE_ROWS)
                    y = fp_operands(rng, "fp16", SERVE_ROWS)
                else:
                    x = int_operands(rng, dtype, SERVE_ROWS)
                    y = int_operands(rng, dtype, SERVE_ROWS,
                                     divisor=op == "div")
                lines.append(json.dumps({
                    "op": op, "dtype": np.dtype(dtype).name,
                    "x": x.tolist(), "y": y.tolist()}))
                wants.append((op, dtype, reference(op, x, y)))
    out = io.StringIO()
    summary, seconds = timed(lambda: serve_pim_batched(
        io.StringIO("\n".join(lines) + "\n"), out))
    responses = [json.loads(line) for line in out.getvalue().splitlines()]
    if len(responses) != len(wants):
        raise AssertionError(f"serve: {len(responses)} responses to "
                             f"{len(wants)} requests")
    for i, (resp, (op, dtype, want)) in enumerate(zip(responses, wants)):
        flagged = {"error", "degraded", "shed"} & set(resp)
        if flagged:
            raise AssertionError(f"serve: request {i} ({op}) came back "
                                 f"with {sorted(flagged)}: {resp}")
        if op == "div":
            got = (np.asarray(resp["q"], np.uint64),
                   np.asarray(resp["r"], np.uint64))
        elif dtype == np.float16:
            got = np.asarray(resp["result"], np.float64).astype(np.float16)
        else:
            got = np.asarray(resp["result"], np.uint64)
        check(f"serve request {i} ({np.dtype(dtype).name} {op})", got, want)
    for key in ("degraded_groups", "shed_requests", "errors"):
        if summary[key]:
            raise AssertionError(f"serve: {key}={summary[key]}")
    rows = len(wants) * SERVE_ROWS
    log(phase="serve", requests=len(wants), rows_per_request=SERVE_ROWS,
        batches=summary["batches"], groups=summary["groups"],
        seconds=seconds, rows_per_s=rows / seconds,
        server_exec_rows_per_s=summary["rows_per_s"],
        degraded_groups=summary["degraded_groups"],
        shed_requests=summary["shed_requests"])


def phase_mosaic(jax, pim, rng) -> None:
    from repro.kernels import ops as kops
    from repro.kernels import pim_exec
    # the static-slice levelized kernel, through the ufunc frontend
    x = fp_operands(rng, "fp16", MOSAIC_ROWS)
    y = fp_operands(rng, "fp16", MOSAIC_ROWS)
    prep = pim.prepare("fp_add", x, y, backend="pallas",
                       schedule="slots-static", chunk_rows=MOSAIC_ROWS)
    stats, _ = run_twice("pallas slots-static fp16 add", prep,
                         reference("fp_add", x, y), MOSAIC_ROWS)
    # inspect the kernel that dispatch cached for this program and plan
    comp = kops.compiled(prep.program, prep.plan)
    r = comp.resolve(prep.program, prep.plan, ("x", "y"))
    run = comp.get_static_pallas(prep.program, prep.plan, ["x", "y"],
                                 r.in_widths, r.out_widths)
    text, compile_s = timed(lambda: run.lower(jax.ShapeDtypeStruct(
        (2, MOSAIC_ROWS), np.uint32)).compile().as_text())
    require_mosaic("make_slots_static", text)
    log(phase="mosaic", kernel="make_slots_static", op="fp_add",
        fmt="fp16", levels=int(r.sched.n_levels), kernel_compile_s=compile_s,
        **stats)

    # the gate-serial kernel
    xs = int_operands(rng, np.uint16, MOSAIC_ROWS)
    ys = int_operands(rng, np.uint16, MOSAIC_ROWS)
    prog = pim.prepare("add", xs, ys).program
    got, seconds = timed(lambda: kops.run_program(
        prog, {"x": xs, "y": ys}, MOSAIC_ROWS, levelized=False,
        backend="pallas"))
    check("pallas gate-serial uint16 add", got["z"], reference("add", xs, ys))
    ops_, a, b, o, n_cells = kops.program_arrays(prog)
    n_words = MOSAIC_ROWS // 32
    text, compile_s = timed(lambda: pim_exec.pim_exec_padded.lower(
        jax.ShapeDtypeStruct((n_cells, n_words), np.uint32),
        ops_, a, b, o, n_cells=n_cells).compile().as_text())
    require_mosaic("pim_exec_padded", text)
    log(phase="mosaic", kernel="pim_exec_padded", op="add", dtype="uint16",
        gates=int(len(ops_)), rows=MOSAIC_ROWS, first_call_s=seconds,
        kernel_compile_s=compile_s)


def phase_four_chips(jax, pim, rng) -> None:
    n_dev = len(jax.devices())
    if n_dev != 4:
        raise AssertionError(f"--four-chips needs a four-chip host, found "
                             f"{n_dev} devices")
    x = fp_operands(rng, "fp16", FULL_ROWS)
    y = fp_operands(rng, "fp16", FULL_ROWS)
    want = reference("fp_add", x, y)
    one = pim.prepare("fp_add", x, y, chunk_rows=FULL_ROWS, shards=1)
    four = pim.prepare("fp_add", x, y, chunk_rows=FULL_ROWS, shards=4)
    if one.plan.mesh is not None or four.plan.mesh is None or \
            four.plan.mesh.devices.size != 4:
        raise AssertionError("four_chips: unexpected meshes "
                             f"{one.plan.mesh} / {four.plan.mesh}")
    outs = []
    for shards, prep in ((1, one), (4, four)):
        stats, got = run_twice(f"fp16 add, 64 Mi rows, shards={shards}",
                               prep, want, FULL_ROWS)
        outs.append(got.view(np.uint16))
        log(phase="four_chips", op="fp_add", fmt="fp16", shards=shards,
            n_devices=n_dev, **stats)
    if not np.array_equal(*outs):
        raise AssertionError("four_chips: shards=4 differs from shards=1")
    mem = [d.memory_stats() or {} for d in jax.devices()]
    log(phase="four_chips", bit_identical=True, n_devices=n_dev,
        peak_bytes_in_use=[m.get("peak_bytes_in_use") for m in mem])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only fp16 add over 64 Mi rows with shards=4 "
                         "against shards=1 (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax, devices = require_tpu()
    from repro import pim_ufunc as pim
    from repro.runtime import compile_cache, telemetry
    log(phase="start", compile_cache=compile_cache.enable(),
        jax=jax.__version__, device_kind=devices[0].device_kind,
        n_devices=len(devices))
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(jax, pim, rng)
    else:
        phase_full_memory(jax, pim, rng)
        phase_families(pim, rng)
        phase_serve(rng)
        phase_mosaic(jax, pim, rng)
    aot_failed = int(telemetry.REGISTRY.counter("pim.cache.aot_failed"))
    if aot_failed:
        raise AssertionError(f"pim.cache.aot_failed={aot_failed}")
    log(phase="end", seconds=time.perf_counter() - t0, aot_failed=aot_failed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
