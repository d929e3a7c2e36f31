"""Work counts of a built gate program, per row.

The NOR gates of a source gate program (``Program.cost().nor_gates``,
the same whatever executor or schedule runs it) and the port bits of a
row, in and out.  The port bits are the least a row's operands and
results take in memory, so bytes over the chip's HBM bandwidth is the
least time the executors could take.  Which programs a call of an op
runs, and so its work per row, is its family's to say
(``bench/families/<family>.py``, ``work``).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class OpWork:
    nor_gates: float
    in_bits: float         # bits of the input ports, per row
    out_bits: float        # bits of the output ports, per row

    @property
    def port_bytes(self) -> float:
        """Bytes a row moves in and out: its port bits over 8."""
        return (self.in_bits + self.out_bits) / 8


def of_program(prog) -> OpWork:
    """Work of one row of the gate program ``prog``."""
    bits = {n: len(cells) for n, cells in prog.ports.items()}
    return OpWork(nor_gates=int(prog.cost().nor_gates),
                  in_bits=sum(bits[n] for n in prog.in_ports),
                  out_bits=sum(bits[n] for n in prog.out_ports))
