"""How unevenly one labelled gauge of ``/metrics`` lies when the window closes:
its largest labelset over the mean of all of them (1.0: even). None where the
program exports no such gauge."""


def read(evidence, args):
    values = [v for k, v in evidence.after["metrics"].items()
              if k.startswith(args["gauge"] + "{")]
    if not values or sum(values) <= 0:
        return None
    return max(values) * len(values) / sum(values)
