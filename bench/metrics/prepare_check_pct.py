"""prepare_check_pct: the share of the traced window inside the union of
the program's ``pim.prepare.check`` spans (the fp operand checks; int
range checks under ``width=``), from the profiled block's ``pim.*``
spans (``bench/spans.py``; ``spans.SHARES``)."""


def read(r):
    if r.spans is None:
        return None
    return r.spans.shares().get("prepare_check_pct")
