"""How far one counter of ``/metrics`` moved over the window."""


def read(evidence, args):
    if args["counter"] not in evidence.after["metrics"]:
        return None
    return evidence.counter(args["counter"])
