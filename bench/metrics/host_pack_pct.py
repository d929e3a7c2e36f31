"""host_pack_pct: the share of the traced window inside the union of the
program's ``pim.dispatch.pack`` spans (per chunk or group: the fused
value block, or the host bit transpose), from the profiled block's
``pim.*`` spans (``bench/spans.py``; ``spans.SHARES``)."""


def read(r):
    if r.spans is None:
        return None
    return r.spans.shares().get("host_pack_pct")
