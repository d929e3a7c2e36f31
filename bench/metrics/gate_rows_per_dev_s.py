"""gate_rows_per_dev_s: NOR gates times rows of every call in the traced
window (``bench/work.py``: the source program's gates, whatever executor
runs it), over the device time of the executor executables
(``pim_exec_*``), summed over chips."""


def read(r):
    t = r.trace
    if t is None or t.executor_s <= 0:
        return None
    gate_rows = sum(c.rows * r.work[c.op].nor_gates for c in r.calls
                    if c.error is None)
    return gate_rows / t.executor_s
