"""A GEMV family for the tests: ``y[m] = sum_k a[m, k] * x[k]`` through
``pim.gemv`` on unsigned operands, exact at full width.

A row is one product, so a call of ``rows`` rows takes a matrix of
``rows / k`` rows of ``k`` (the configuration's ``k``) and one vector of
``k``.  The configuration's ``operands`` group gives ``a_min`` and
``x_min``.  The tests copy this file into a bench root of their own as
``bench/families/gemv.py``; it shows what a family file holds
(``bench/harness.py``), and that one can be added without an edit to the
harness.
"""

import numpy as np

from bench import traffic, work as bwork

#: ``pim.gemv`` has no request form on ``--pim-serve``.
WIRE = None


def _lanes(rows: int, k: int) -> int:
    if rows % k:
        raise ValueError(f"a GEMV call of {rows} rows is no whole number "
                         f"of matrix rows of {k}")
    return rows // k


def operands(rng, config: dict, rows: int):
    k, spec, dtype = int(config["k"]), config["operands"], config["dtype"]
    m = _lanes(rows, k)
    a = traffic.int_operands(rng, dtype, m * k, spec["a_min"]).reshape(m, k)
    return a, traffic.int_operands(rng, dtype, k, spec["x_min"])


def head(operands, rows: int):
    a, x = operands
    return a[:_lanes(rows, len(x))], x


def call(lib, op: str, a, x):
    return lib.pim.gemv(a, x, **lib.kw)


def traced_call(lib, span, op: str, a, x):
    """``pim.gemv`` has no prepared form, so its front end and decode run
    inside the dispatch span; the program's own ``pim.*`` spans split
    it."""
    with span(f"bench.dispatch:{op}"):
        return call(lib, op, a, x)


def plan_of(lib, op: str, a, x):
    """The shards of the plan the module defaults give operands of this
    type; every product row goes in one dispatch, so a call is its own
    chunk."""
    plan = lib.pim.prepare("mul", x[:1], x[:1], **lib.kw).plan
    shards = 1 if plan.mesh is None else int(plan.mesh.devices.size)
    return shards, a.size


def reference(op: str, a, x):
    """Each ``y[m]`` as a Python int: the matrix split into two
    half-width limbs, so that every partial sum fits in uint64."""
    width = 8 * a.dtype.itemsize
    half = width // 2
    if a.dtype.kind != "u" or width + half + a.shape[1].bit_length() > 64:
        raise ValueError(f"no exact uint64 GEMV of {a.dtype} by "
                         f"{a.shape[1]}")
    a64, x64 = a.astype(np.uint64), x.astype(np.uint64)
    lo = (a64 & np.uint64((1 << half) - 1)) @ x64
    hi = (a64 >> np.uint64(half)) @ x64
    return (hi.astype(object) << half) + lo.astype(object)


def control(op: str, a, x):
    """The sums wrapped to the operands' width."""
    return reference(op, a, x) & ((1 << 8 * a.dtype.itemsize) - 1)


def mismatched(op: str, got, want, rows: int) -> int:
    """The rows summed into each output that differs (``rows / M`` each);
    every row counts when the shapes differ."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return rows
    return rows // len(want) * int(np.count_nonzero(got != want))


def work(op: str, config: dict) -> bwork.OpWork:
    """Per product row: the multiply's gates, and those of each adder
    level on the rows it adds (``k`` padded to a power of two, as
    ``pim.gemv`` pads it); the operands' bits in, each output's bits out
    once over its ``k`` rows."""
    from repro.core.pim_numerics import program_for
    width = 8 * np.dtype(config["dtype"]).itemsize
    k = int(config["k"])
    m = _lanes(int(config["rows"]), k)
    levels = (k - 1).bit_length()
    mul = bwork.of_program(program_for("int-serial", "mul", width))
    gates = m * (1 << levels) * mul.nor_gates + sum(
        m * (1 << (levels - lv)) * bwork.of_program(program_for(
            "int-serial", "add", 2 * width + lv - 1)).nor_gates
        for lv in range(1, levels + 1))
    return bwork.OpWork(nor_gates=gates / (m * k), in_bits=mul.in_bits,
                        out_bits=(2 * width + levels) / k)
