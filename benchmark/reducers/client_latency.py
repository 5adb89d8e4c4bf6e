"""Mean client-side latency of one endpoint's requests completed in the
window, in ms; with ``minus_span``, less the mean of the program's root span of
that name over the same window (what the wire and the server's loop add)."""


def read(evidence, args):
    done = evidence.completed(args["endpoint"])
    if not done:
        return None
    mean = sum(r["done"] - r["sent"] for r in done) / len(done) * 1000.0
    if "minus_span" in args:
        ms, n = evidence.span(args["minus_span"])
        if n <= 0:
            return None
        mean -= ms / n
    return mean
