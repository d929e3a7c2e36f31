"""serve_queue_pct: the share of the client's latency that the traced
block's requests spent in the server's admission queue: the sum of the
responses' ``queue_us`` (admission to the start of the request's batch)
over the sum of the requests' latencies (arrival to response in hand)."""


def read(r):
    timed = [c for c in r.calls if c.queue_s is not None]
    if r.trace is None or not timed:
        return None
    return 100.0 * sum(c.queue_s for c in timed) / sum(c.seconds
                                                       for c in timed)
