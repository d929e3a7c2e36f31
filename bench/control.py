#!/usr/bin/env python3
"""The control of ``correct``: the plain reference one precision down,
put in the program's place, run through the harness at a cell's own
size.  Every seed has to come out not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

The benchmark's own runs never run this.  It prints one JSON line per
seed, with the numbers compared beside their limits.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Control:
    """``reference.control`` in the program's place; the plan check
    still asks the program, so that the control runs where the cell
    does."""

    def __init__(self, program):
        from bench import reference
        self.ref, self.program = reference, program

    def call(self, op, x, y):
        return self.ref.as_result(op, self.ref.control(op, x, y), x.dtype)

    def plan_of(self, op, x, y):
        return self.program.plan_of(op, x, y)

    def counters(self):
        return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        jax, devices = harness.start_jax(cell.chips)
        system = Control(harness.Ufuncs(cell.config))
        for seed in args.seeds:
            line = harness.run_cell(
                cell, seed, args.seconds, False, jax=jax, devices=devices,
                system=system, t_process=time.perf_counter(),
                emit=lambda d: None)
            print(json.dumps({"seed": seed, "correct": line["correct"],
                              "attempted": line["attempted"],
                              "checks": line["checks"]}), flush=True)
    except harness.BenchError as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
