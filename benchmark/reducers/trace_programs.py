"""Device-plane time of the jitted programs (the trace's ``XLA Modules``
events inside the window) per query that a device route served, in ms."""

from reducers.routes import device_queries


def read(evidence, args):
    if evidence.trace is None or not evidence.trace["programs"]:
        return None
    queries = device_queries(evidence, args["device_routes"])
    if queries <= 0:
        return None
    seconds = sum(p["seconds"] for p in evidence.trace["programs"].values())
    return seconds * 1000.0 / queries
