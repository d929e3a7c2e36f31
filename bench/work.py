"""Work counts of the ops the benchmark drives, from the built programs.

For each op: the NOR gates of its source gate program
(``Program.cost().nor_gates``, the same whatever executor or schedule
runs it) and the port bits of a row, in and out.  The port bits are the
least a row's operands and results take in memory, so bytes over the
chip's HBM bandwidth is the least time the executors could take.
"""

from __future__ import annotations

import dataclasses
import functools

#: Operand dtype of a configuration -> the program builders' parameter.
_FP_FORMATS = {"float16": "fp16", "float32": "fp32", "float64": "fp64"}
_INT_WIDTHS = {"uint8": 8, "uint16": 16, "uint32": 32, "uint64": 64}
#: A configuration's ``algorithm`` -> the builders' kind suffix.
_KINDS = {"bit-serial": "serial", "bit-parallel": "parallel"}


@dataclasses.dataclass(frozen=True)
class OpWork:
    nor_gates: int
    in_bits: int           # bits of the input ports, per row
    out_bits: int          # bits of the output ports, per row

    @property
    def port_bytes(self) -> float:
        """Bytes a row moves in and out: its port bits over 8."""
        return (self.in_bits + self.out_bits) / 8


@functools.lru_cache(maxsize=None)
def op_work(op: str, dtype: str, algorithm: str) -> OpWork:
    """Work of one public ufunc op (``fp_add``, ``mul``, ...) on operands
    of ``dtype`` by ``algorithm`` (``bit-serial`` or ``bit-parallel``),
    as ``pim_ufunc`` builds it."""
    from repro.core.pim_numerics import program_for
    kind = _KINDS[algorithm]
    if op.startswith("fp_"):
        prog = program_for(f"fp-{kind}", op[3:], _FP_FORMATS[dtype])
    else:
        prog = program_for(f"int-{kind}", op, _INT_WIDTHS[dtype])
    bits = {n: len(cells) for n, cells in prog.ports.items()}
    return OpWork(nor_gates=int(prog.cost().nor_gates),
                  in_bits=sum(bits[n] for n in prog.in_ports),
                  out_bits=sum(bits[n] for n in prog.out_ports))
