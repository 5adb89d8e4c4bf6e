"""1 - union of the device's operation intervals over the traced window, %."""


def read(evidence, args):
    if evidence.trace is None or evidence.trace["busy_s"] <= 0:
        return None
    return 100.0 * evidence.trace["idle_share"]
