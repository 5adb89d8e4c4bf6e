"""Inclusive ms of one span path of ``/debug/profile`` gained over the window,
per root span ``per``. ``minus_metric`` takes another per-layer metric off
(``minus_scaled_by``: a share in % of the queries that metric is counted per)."""


def read(evidence, args):
    ms, _ = evidence.span(args["path"])
    _, roots = evidence.span(args["per"])
    if roots <= 0:
        return None
    value = ms / roots
    if "minus_metric" in args:
        other = evidence.metric(args["minus_metric"])
        if other is None:
            return None
        share = 100.0
        if "minus_scaled_by" in args:
            share = evidence.metric(args["minus_scaled_by"])
            if share is None:
                return None
        value -= other * share / 100.0
    return value
