"""dispatch_host_pct: the share of the traced window inside the dispatch
span (``kernels.ops.run_program_streaming``: pack, transfer, streaming,
unpack) during which no device operation runs on any chip."""


def read(r):
    t = r.trace
    if t is None or t.window_s <= 0 or "dispatch" not in t.span_s:
        return None
    return 100.0 * t.dispatch_host_s / t.window_s
