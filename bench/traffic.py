"""The one traffic generator: reads a mix's data file and a
configuration, and makes the operands and the calls from the seed.

A mix (``bench/traffic/<mix>.json``) holds only parameters:

``path``        the entry point the calls go through: ``"ufunc"`` (the
                default), one library call each in the harness's own
                loop; ``"serve"``, one request line each to the batched
                JSON-lines server (``harness.Served``).
``arrivals``    ``"closed"``: one caller, each call sent when the last
                returned; ``"poisson"``: calls arrive at ``rate_per_s``
                on average, and a call's latency counts its wait from its
                arrival (on the ``ufunc`` path one caller serves them in
                order; on the ``serve`` path a writer sends each at its
                arrival, whatever is still in flight).
``rate_per_s``  the offered rate of a ``"poisson"`` mix.
``op_counts``   how many calls of each op a block holds (default: one of
                each of the configuration's ``ops``).
``rows``        the row counts a block's calls take, each op at each
                (default: the configuration's ``rows``).
``row_counts``  instead of ``rows``: row count -> how many calls of that
                size a block holds for each call that ``op_counts``
                names.
``order``       ``"rotation"``: a block keeps its ops in turn and the seed
                picks the op that starts; ``"shuffled"``: the seed orders
                the block.
``pool``        how many distinct operand sets the run draws; the
                window's ``i``-th call takes set ``i % pool``, so no two
                calls in a row send the same data.
``row_shards``  how many chips the default plan must spread each call's
                rows over (the harness refuses a run where it does not).

A block is the unit of work: every seed sends the same calls, sizes and
arrival gaps in a block, in its own order.  The window runs whole blocks.
The same seed gives the same operands and the same calls.

What an operand set is, and what a call of so many rows takes of it, is
the configuration's family's to say (``bench/families/<family>.py``:
``operands`` draws one set from the run's generator, ``head`` cuts a
call's operands from it); the value generators here
(:func:`fp_operands`, :func:`int_operands`) serve every family.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

PATHS = ("ufunc", "serve")
ARRIVALS = ("closed", "poisson")
ORDERS = ("rotation", "shuffled")


@dataclasses.dataclass(frozen=True)
class Send:
    op: str
    rows: int
    gap_s: float           # from the previous arrival; 0 in a closed loop


@dataclasses.dataclass
class Traffic:
    block: List[Send]      # one block, in the order it is sent
    pool: List[tuple]      # operand sets, each from the family's operands
    open_loop: bool
    row_shards: int
    family: object         # the configuration's family module
    path: str = "ufunc"

    @property
    def shapes(self) -> List[Tuple[str, int]]:
        """Every (op, rows) the block sends, once each."""
        return sorted({(s.op, s.rows) for s in self.block})

    def pair(self, i: int) -> int:
        """The operand set of the window's ``i``-th call."""
        return i % len(self.pool)

    def head(self, pair: int, rows: int) -> tuple:
        """The operands of a call of ``rows`` rows on pool set ``pair``."""
        return self.family.head(self.pool[pair], rows)

    def operands(self, i: int, s: Send) -> tuple:
        """The operands of the window's ``i``-th call."""
        return self.head(self.pair(i), s.rows)


def rng_for(seed: int) -> np.random.Generator:
    """The run's generator: any whole number from 0 up, of any size."""
    if seed < 0:
        raise ValueError(f"--seed must be 0 or more, got {seed}")
    return np.random.default_rng(seed)


def fp_operands(rng, dtype: str, n: int, exp_min: int, exp_max: int):
    """Random normal-range IEEE floats of any numpy float ``dtype``:
    random sign and mantissa, unbiased exponent uniform in
    [exp_min, exp_max]."""
    fi = np.finfo(dtype)
    bits = np.dtype(f"u{fi.bits // 8}")
    bias = (1 << (fi.nexp - 1)) - 1
    if not 1 <= bias + exp_min <= bias + exp_max < (1 << fi.nexp) - 1:
        raise ValueError(f"exponents {exp_min}..{exp_max} leave the normal "
                         f"range of {dtype}")
    s = rng.integers(0, 2, n, dtype=bits) << bits.type(fi.bits - 1)
    e = rng.integers(bias + exp_min, bias + exp_max + 1, n, dtype=bits)
    m = rng.integers(0, 1 << fi.nmant, n, dtype=bits)
    return (s | (e << bits.type(fi.nmant)) | m).view(dtype)


def int_operands(rng, dtype: str, n: int, low: int):
    """Uniform unsigned integers of ``dtype`` in [low, 2**width)."""
    dt = np.dtype(dtype)
    if dt.kind != "u":
        raise ValueError(f"no int operand generator for {dtype!r}")
    return rng.integers(low, 1 << (8 * dt.itemsize), n, dtype=np.uint64
                        ).astype(dt)


def _gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps of mean ``1/rate``: the quantiles at the
    middle of ``n`` equal steps, so that every seed gets the same gaps."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def sizes_of(mix: dict, config: dict) -> List[Tuple[int, int]]:
    """(rows, calls of that size per op call) of a mix: ``row_counts``, or
    one call at each of ``rows``."""
    if "row_counts" in mix:
        if "rows" in mix:
            raise ValueError(f"a mix gives rows or row_counts, not both: "
                             f"{mix}")
        sizes = [(int(r), int(k)) for r, k in mix["row_counts"].items()]
    else:
        sizes = [(int(r), 1) for r in mix.get("rows", [config["rows"]])]
    if not sizes or min(min(r, k) for r, k in sizes) < 1:
        raise ValueError(f"bad row counts in mix {mix}")
    return sizes


def make(mix: dict, config: dict, seed: int, family) -> Traffic:
    """The run's traffic: the block of calls, and ``pool`` operand sets
    of ``family`` (a module of ``bench/families``), each covering the
    mix's largest call."""
    path = mix.get("path", "ufunc")
    arrivals = mix.get("arrivals", "closed")
    order = mix.get("order", "rotation")
    if path not in PATHS or arrivals not in ARRIVALS or order not in ORDERS:
        raise ValueError(f"unknown path, arrivals or order in mix {mix}")
    rng = rng_for(seed)
    counts = mix.get("op_counts", {op: 1 for op in config["ops"]})
    unknown = set(counts) - set(config["ops"])
    if unknown:
        raise ValueError(f"the mix sends {sorted(unknown)}, which the "
                         f"configuration does not have")
    sizes = sizes_of(mix, config)
    calls = [(op, r) for op, k in counts.items() for _ in range(int(k))
             for r, m in sizes for _ in range(m)]
    if order == "rotation":
        start = int(rng.integers(0, len(calls)))
        calls = calls[start:] + calls[:start]
    else:
        calls = [calls[i] for i in rng.permutation(len(calls))]
    if arrivals == "poisson":
        rate = float(mix["rate_per_s"])
        if not rate > 0:
            raise ValueError(f"bad arrival rate in mix {mix}")
        gaps = _gaps(rate, len(calls))
        gaps = gaps[rng.permutation(len(gaps))]
    else:
        gaps = np.zeros(len(calls))
    pool = int(mix.get("pool", 1))
    sets = [family.operands(rng, config, max(r for r, _ in sizes))
            for _ in range(pool)]
    block = [Send(op, r, float(g)) for (op, r), g in zip(calls, gaps)]
    return Traffic(block=block, pool=sets, open_loop=arrivals == "poisson",
                   row_shards=int(mix["row_shards"]), family=family,
                   path=path)
