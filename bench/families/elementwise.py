"""The elementwise family: one op of ``repro.pim_ufunc`` (``add`` ...
``div``, ``fp_add`` ... ``fp_div``) on two 1-D operands of equal length;
a row is one element.  A configuration that names no ``family`` is of
this one.

The operands come from the configuration's ``dtype`` and ``operands``
group (``traffic.fp_operands``, ``traffic.int_operands``).

The reference is host numpy arithmetic and imports nothing of the
program (its logic is copied from ``chip_smoke.py``): IEEE binary32
round-to-nearest-even in numpy's own float32 ops, and exact unsigned
integers in uint64 (a 33-bit sum, a 64-bit product, the quotient with its
remainder).  The control breaks the configuration's guarantee the way a
tempting shortcut would: the float result rounded to the nearest format
below (float32 to bfloat16, float16 to fp8 e5m2, float64 to float32's
mantissa), the integer result wrapped to the operands' width.

Work per row: the NOR gates of the op's source gate program
(``Program.cost().nor_gates``, the same whatever executor or schedule
runs it) and its port bits, in and out (``work.of_program``).

The wire form (:data:`WIRE`) is that of ``--pim-serve``.
"""

import functools

import numpy as np

from bench import reference as ref
from bench import traffic, work as bwork

_FP = {"fp_add": np.add, "fp_mul": np.multiply, "fp_div": np.divide}
_UINT = {np.dtype(np.float16): np.uint16, np.dtype(np.float32): np.uint32,
         np.dtype(np.float64): np.uint64}
#: Mantissa bits of the format one precision below each float dtype.
_CONTROL_MANTISSA = {np.dtype(np.float64): 23, np.dtype(np.float32): 7,
                     np.dtype(np.float16): 2}
#: Operand dtype of a configuration -> the program builders' parameter.
_FP_FORMATS = {"float16": "fp16", "float32": "fp32", "float64": "fp64"}
_INT_WIDTHS = {"uint8": 8, "uint16": 16, "uint32": 32, "uint64": 64}
#: A configuration's ``algorithm`` -> the builders' kind suffix.
_KINDS = {"bit-serial": "serial", "bit-parallel": "parallel"}


# --------------------------------------------------------------------------
# operands and calls
# --------------------------------------------------------------------------

def operands(rng, config: dict, rows: int):
    """One operand pair of ``rows`` elements from the run's generator."""
    dtype, spec = config["dtype"], config["operands"]
    if np.dtype(dtype).kind == "f":
        return tuple(traffic.fp_operands(rng, dtype, rows, spec["exp_min"],
                                         spec["exp_max"]) for _ in range(2))
    return (traffic.int_operands(rng, dtype, rows, spec["x_min"]),
            traffic.int_operands(rng, dtype, rows, spec["y_min"]))


def head(pair, rows: int):
    """A call's operands for its row count: the pair's first ``rows``."""
    x, y = pair
    return x[:rows], y[:rows]


def call(lib, op: str, x, y):
    return getattr(lib.pim, op)(x, y, **lib.kw)


def traced_call(lib, span, op: str, x, y):
    """What ``Prepared.run`` does, with a span around each layer."""
    with span(f"bench.prepare:{op}"):
        prep = lib.pim.prepare(op, x, y, **lib.kw)
    with span(f"bench.dispatch:{op}"):
        outs = lib.kops.run_program_streaming(
            prep.program, prep.inputs, prep.n_rows, prep.plan)
    with span(f"bench.finish:{op}"):
        return prep.finish(outs)


def plan_of(lib, op: str, x, y):
    """(row shards, chunk rows) of the plan a call of ``op`` takes."""
    plan = lib.pim.prepare(op, x[:1], y[:1], **lib.kw).plan
    shards = 1 if plan.mesh is None else int(plan.mesh.devices.size)
    return shards, int(plan.effective_chunk_rows)


# --------------------------------------------------------------------------
# the reference and its control
# --------------------------------------------------------------------------

def reference(op: str, x: np.ndarray, y: np.ndarray):
    """The exact result of ``op``: the result's bit pattern as unsigned
    integers for floats, uint64 values for integers, ``(q, r)`` for
    ``div``."""
    if op in _FP:
        return _FP[op](x, y).view(_UINT[x.dtype])
    a, b = x.astype(np.uint64), y.astype(np.uint64)
    if op == "add":
        return a + b
    if op == "mul":
        return a * b
    if op == "div":
        return a // b, a % b
    raise ValueError(f"no reference for op {op!r}")


def control(op: str, x: np.ndarray, y: np.ndarray):
    """The reference one precision down, in the form the ufunc returns:
    float results rounded to the format below, integer results wrapped
    to the operands' width."""
    if op in _FP:
        return as_result(op, ref.round_mantissa(
            reference(op, x, y), x.dtype, _CONTROL_MANTISSA[x.dtype]),
            x.dtype)
    mask = np.uint64((1 << (8 * x.dtype.itemsize)) - 1)
    exact = reference(op, x, y)
    if op == "div":
        return tuple(part & mask for part in exact)
    return exact & mask


def as_result(op: str, bits, dtype: np.dtype):
    """A reference or control result in the form the ufunc returns:
    floats in ``dtype`` for the fp ops, uint64 otherwise."""
    if op in _FP:
        return np.asarray(bits).astype(_UINT[np.dtype(dtype)]).view(dtype)
    return bits


def mismatched(op: str, got, want, rows: int) -> int:
    """Rows of ``got`` (the ufunc's result) that differ from ``want``
    (:func:`reference`); every row counts when the shapes differ."""
    parts_got = got if isinstance(got, tuple) else (got,)
    parts_want = want if isinstance(want, tuple) else (want,)
    n = parts_want[0].shape[0]
    if len(parts_got) != len(parts_want):
        return n
    bad = np.zeros(n, bool)
    for g, w in zip(parts_got, parts_want):
        g = np.asarray(g)
        if g.dtype.kind == "f":
            g = g.view(w.dtype)
        if g.shape != w.shape:
            return n
        bad |= g != w
    return int(np.count_nonzero(bad))


# --------------------------------------------------------------------------
# work and the wire
# --------------------------------------------------------------------------

def work(op: str, config: dict) -> bwork.OpWork:
    return op_work(op, config["dtype"], config["algorithm"])


@functools.lru_cache(maxsize=None)
def op_work(op: str, dtype: str, algorithm: str) -> bwork.OpWork:
    """Work of one public ufunc op (``fp_add``, ``mul``, ...) on operands
    of ``dtype`` by ``algorithm`` (``bit-serial`` or ``bit-parallel``),
    as ``pim_ufunc`` builds it."""
    from repro.core.pim_numerics import program_for
    kind = _KINDS[algorithm]
    if op.startswith("fp_"):
        return bwork.of_program(
            program_for(f"fp-{kind}", op[3:], _FP_FORMATS[dtype]))
    return bwork.of_program(program_for(f"int-{kind}", op, _INT_WIDTHS[dtype]))


class Wire:
    """The JSON-lines form of ``--pim-serve``: a request holds ``op``,
    ``dtype``, ``x`` and ``y``; a response ``op``, ``rows`` and
    ``result``, or ``q`` and ``r`` for ``div``."""

    @staticmethod
    def request(config: dict, op: str, x, y) -> dict:
        return {"op": op, "dtype": config["dtype"], "x": x.tolist(),
                "y": y.tolist()}

    @staticmethod
    def parse(req: dict):
        """(op, operands) of a request line's object."""
        return req["op"], (np.asarray(req["x"], req["dtype"]),
                           np.asarray(req["y"], req["dtype"]))

    @staticmethod
    def response(op: str, operands, out) -> dict:
        """The response object that answers ``operands`` with ``out``, a
        result in the form the ufunc returns."""
        resp = {"op": op, "rows": len(operands[0])}
        if isinstance(out, tuple):
            resp["q"], resp["r"] = (part.tolist() for part in out)
        else:
            resp["result"] = out.tolist()
        return resp

    @staticmethod
    def result(config: dict, resp: dict):
        """The result a response line's object carries."""
        dtype = config["dtype"]
        dtype = dtype if np.dtype(dtype).kind == "f" else np.uint64
        if "result" in resp:
            return np.asarray(resp["result"], dtype)
        return np.asarray(resp["q"], dtype), np.asarray(resp["r"], dtype)


WIRE = Wire()
