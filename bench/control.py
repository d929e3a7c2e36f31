#!/usr/bin/env python3
"""The control of ``correct``: the plain reference one precision down
(the ``control`` of the cell's family, ``bench/families/<family>.py``),
put in the program's place, run through the harness at a cell's own
size.  Every seed has to come out not correct.  On the ``serve`` path
the control is a server of the family's wire format
(:func:`serve_control`), driven by the harness's own client.

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
    """The family's ``control`` in the program's place; the plan check
    still asks the program, so that the control runs where the cell
    does."""

    def __init__(self, program):
        self.family, self.program = program.family, program

    def call(self, op, *operands):
        return self.family.control(op, *operands)

    def plan_of(self, op, *operands):
        return self.program.plan_of(op, *operands)

    def counters(self):
        return {}


def serve_control(family):
    """A JSON-lines server that answers each request line, in order, with
    the family's ``control`` of its operands, in the family's wire
    format."""
    wire = family.WIRE

    def serve(inp, outp) -> None:
        for line in inp:
            op, operands = wire.parse(json.loads(line))
            resp = dict(wire.response(op, operands,
                                      family.control(op, *operands)),
                        queue_us=0.0)
            print(json.dumps(resp), file=outp, flush=True)
    return serve


def control_system(harness, cell):
    """The control in the place of the system the cell's mix names."""
    if cell.mix.get("path", "ufunc") == "serve":
        return harness.Served(cell.config, cell.family,
                              serve=serve_control(cell.family))
    return Control(harness.Ufuncs(cell.config, cell.family))


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
        for seed in args.seeds:
            system = control_system(harness, cell)
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
