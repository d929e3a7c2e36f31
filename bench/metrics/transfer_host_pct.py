"""transfer_host_pct: the share of the traced window inside the union of
the program's ``pim.dispatch.h2d`` and ``pim.dispatch.d2h`` spans (the
data block to the chip, the result back), from the profiled block's
``pim.*`` spans (``bench/spans.py``; ``spans.SHARES``)."""


def read(r):
    if r.spans is None:
        return None
    return r.spans.shares().get("transfer_host_pct")
