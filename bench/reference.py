"""Plain arithmetic that the references and controls of more than one
family can share; it imports nothing of the program.

Each family's own reference, control and comparison live in its file,
``bench/families/<family>.py`` (the elementwise ops' in
``bench/families/elementwise.py``), beside its operands and calls; the
harness asks the cell's family for them (``harness.check``,
``bench/control.py``).
"""

from __future__ import annotations

import numpy as np


def round_mantissa(bits: np.ndarray, dtype, keep: int) -> np.ndarray:
    """Bit patterns of ``dtype`` rounded to ``keep`` mantissa bits
    (nearest, ties to even), widened back to ``dtype``'s patterns."""
    drop = np.uint64(np.finfo(dtype).nmant - keep)
    b = bits.astype(np.uint64)
    half = (np.uint64(1) << drop) >> np.uint64(1)
    b = b + (half - np.uint64(1)) + ((b >> drop) & np.uint64(1))
    return ((b >> drop) << drop).astype(bits.dtype)
