"""What one counter of ``/metrics`` reads when the window closes: its total
since the process started, for work that set-up does and the window does not
(a counter's movement over the window is ``counter_delta``)."""


def read(evidence, args):
    return evidence.after["metrics"].get(args["counter"])
