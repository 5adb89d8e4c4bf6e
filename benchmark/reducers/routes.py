"""How many of the window's queries each route of ``query_stats`` served, from
the ``horaedb_query_route_total`` counters."""

import re


def by_route(evidence) -> dict[str, float]:
    out = {}
    for key, moved in evidence.counters("horaedb_query_route_total{").items():
        m = re.search(r'route="([^"]*)"', key)
        if m and moved:
            out[m.group(1)] = out.get(m.group(1), 0.0) + moved
    return out


def device_queries(evidence, device_routes) -> float:
    return sum(n for route, n in by_route(evidence).items()
               if route.startswith(tuple(device_routes)))
