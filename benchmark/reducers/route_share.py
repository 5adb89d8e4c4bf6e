"""Share of the window's queries that a device route served, in %."""

from reducers.routes import by_route, device_queries


def read(evidence, args):
    total = sum(by_route(evidence).values())
    if total <= 0:
        return None
    return 100.0 * device_queries(evidence, args["device_routes"]) / total
