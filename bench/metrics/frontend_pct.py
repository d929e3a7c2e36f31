"""frontend_pct: the share of the traced window that the host spends in
the front end, ``pim.prepare`` (parse, validate, the fp operand checks)
and ``Prepared.finish`` (decode), from the harness's spans; nothing
where the run has none of them (the ``serve`` path)."""


def read(r):
    t = r.trace
    if t is None or t.window_s <= 0 or not {"prepare", "finish"} & set(
            t.span_s):
        return None
    return 100.0 * (t.span_s.get("prepare", 0.0)
                    + t.span_s.get("finish", 0.0)) / t.window_s
