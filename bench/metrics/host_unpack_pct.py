"""host_unpack_pct: the share of the traced window inside the union of the
program's ``pim.dispatch.unpack`` and ``pim.dispatch.concat`` spans (per
chunk or group: the results back to row values; once a streamed call:
its parts joined), from the profiled block's ``pim.*`` spans
(``bench/spans.py``; ``spans.SHARES``)."""


def read(r):
    if r.spans is None:
        return None
    return r.spans.shares().get("host_unpack_pct")
