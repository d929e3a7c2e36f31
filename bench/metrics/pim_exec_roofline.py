"""pim_exec_roofline: the least time the executors could take -- the port
bytes of every row in and out (``bench/work.py``) over the chips' HBM
bandwidth (``bench/peaks.json``) -- as a share of the device time of the
executor executables (``pim_exec_*``) that ran in the traced window,
summed over the chips.

HBM bounds it: no peak of the vector unit's bitwise ops is published, so
no compute bound is stated."""


def read(r):
    t = r.trace
    if t is None or t.executor_s <= 0:
        return None
    nbytes = sum(c.rows * r.work[c.op].port_bytes for c in r.calls
                 if c.error is None)
    return 100.0 * nbytes / r.peaks["hbm_bytes_per_s"] / t.executor_s
