"""prepare_cast_pct: the share of the traced window inside the union of the
program's ``pim.prepare.cast`` spans (broadcast, ravel, views and casts
of the operands), from the profiled block's ``pim.*`` spans
(``bench/spans.py``; ``spans.SHARES``)."""


def read(r):
    if r.spans is None:
        return None
    return r.spans.shares().get("prepare_cast_pct")
