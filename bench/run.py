#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, in this one process, on a TPU with
exactly the chips the cell asks for; anything else exits nonzero with no
result.  ``--trace 0`` measures the end-to-end metrics over a window of
``--seconds``; ``--trace 1`` profiles one block of the mix and reports
the per-layer metrics.  The last line of standard output is the result
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
``checks`` last: each number compared beside its limit).
"""

import os
import sys
import time


def _process_start() -> float:
    """The process's start on the ``time.perf_counter`` clock (Linux:
    from ``/proc/self/stat``; elsewhere, now)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - \
            ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - max(age, 0.0)


T_PROCESS = _process_start()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import json  # noqa: E402

#: glibc ``mallopt`` parameters, fixed at the ceilings that glibc's own
#: dynamic rule raises them to (64-bit): blocks under 32 MiB come from the
#: heap, and the heap is trimmed past 64 MiB free.
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3
MALLOC_THRESHOLDS = {_M_MMAP_THRESHOLD: 32 << 20, _M_TRIM_THRESHOLD: 64 << 20}


def steady_allocator() -> None:
    """Fix the C allocator's thresholds before any large allocation.

    Left to glibc's dynamic rule, whether a freed block of some MiB goes
    back to the system depends on the process's history, so the host
    pack of one process can page-fault its temporaries afresh on every
    chunk while another reuses them: on a TPU v5e host the same uint32
    call took 20-35% longer in some processes than in others.  Fixed
    thresholds make every run take the same path."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        print("bench: no glibc mallopt; allocator thresholds left as they "
              "are", file=sys.stderr)
        return
    for param, value in MALLOC_THRESHOLDS.items():
        if mallopt(param, value) != 1:
            print(f"bench: mallopt({param}, {value}) refused",
                  file=sys.stderr)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    steady_allocator()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        jax, devices = harness.start_jax(cell.chips, bool(args.trace))
        line = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), jax=jax,
            devices=devices, system=harness.make_system(cell),
            t_process=T_PROCESS,
            emit=lambda d: print(json.dumps(d), flush=True))
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
