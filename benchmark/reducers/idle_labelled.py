"""Share of the traced window's idle seconds whose gap label (the shortest
host span over the gap's middle, ``reduce_trace._label_gaps``) starts with
``prefix``, in %: how much of the device's idle time the program's own spans
put a name to. The gaps counted are those of ``evidence.trace["idle_gaps"]``
(the ten largest labels), over ``window_s`` - ``busy_s``."""


def read(evidence, args):
    trace = evidence.trace
    if trace is None:
        return None
    idle_s = trace["window_s"] - trace["busy_s"]
    if idle_s <= 0:
        return None
    labelled = sum(seconds for label, seconds in trace["idle_gaps"]
                   if label.startswith(args["prefix"]))
    return 100.0 * labelled / idle_s
