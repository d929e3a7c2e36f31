"""device_wait_pct: the share of the traced window inside the union of the
program's ``pim.dispatch.wait`` spans (the host blocked on the chip),
from the profiled block's ``pim.*`` spans (``bench/spans.py``;
``spans.SHARES``)."""


def read(r):
    if r.spans is None:
        return None
    return r.spans.shares().get("device_wait_pct")
