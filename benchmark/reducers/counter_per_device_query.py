"""How far one counter of ``/metrics`` moved over the window, per query that a
device route served."""

from reducers.routes import device_queries


def read(evidence, args):
    queries = device_queries(evidence, args["device_routes"])
    if queries <= 0:
        return None
    return evidence.counter(args["counter"]) / queries
