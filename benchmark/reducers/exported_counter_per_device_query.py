"""``counter_per_device_query`` for a counter that not every tree of the
program has: None where ``/metrics`` does not export it (a counter that is
exported and did not move reads 0)."""

from reducers import counter_per_device_query


def read(evidence, args):
    if args["counter"] not in evidence.after["metrics"]:
        return None
    return counter_per_device_query.read(evidence, args)
