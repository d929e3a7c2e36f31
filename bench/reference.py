"""The plain reference, and its control.

The reference is host numpy arithmetic and imports nothing of the
program (its logic is copied from ``chip_smoke.py``): IEEE binary32
round-to-nearest-even in numpy's own float32 ops, and exact unsigned
integers in uint64 (a 33-bit sum, a 64-bit product, the quotient with its
remainder).  The control breaks the configuration's guarantee the way a
tempting shortcut would: the float result rounded to the nearest format
below (float32 to bfloat16, float16 to fp8 e5m2, float64 to float32's
mantissa), the integer result wrapped to the operands' width.
"""

from __future__ import annotations

import numpy as np

_FP = {"fp_add": np.add, "fp_mul": np.multiply, "fp_div": np.divide}
_UINT = {np.dtype(np.float16): np.uint16, np.dtype(np.float32): np.uint32,
         np.dtype(np.float64): np.uint64}
#: Mantissa bits of the format one precision below each float dtype.
_CONTROL_MANTISSA = {np.dtype(np.float64): 23, np.dtype(np.float32): 7,
                     np.dtype(np.float16): 2}


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


def round_mantissa(bits: np.ndarray, dtype, keep: int) -> np.ndarray:
    """Bit patterns of ``dtype`` rounded to ``keep`` mantissa bits
    (nearest, ties to even), widened back to ``dtype``'s patterns."""
    drop = np.uint64(np.finfo(dtype).nmant - keep)
    b = bits.astype(np.uint64)
    half = (np.uint64(1) << drop) >> np.uint64(1)
    b = b + (half - np.uint64(1)) + ((b >> drop) & np.uint64(1))
    return ((b >> drop) << drop).astype(bits.dtype)


def control(op: str, x: np.ndarray, y: np.ndarray):
    """The reference one precision down: float results rounded to the
    format below, integer results wrapped to the operands' width."""
    if op in _FP:
        return round_mantissa(reference(op, x, y), x.dtype,
                              _CONTROL_MANTISSA[x.dtype])
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


def mismatched_rows(op: str, got, want) -> int:
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
