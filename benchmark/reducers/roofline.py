"""A full scan's share of its roofline, in %: the least time the chip could
take for the statement over the kernel time per device-served query (the per-layer
metric that ``kernel_metric`` names). The bound
is HBM: the one-hot matmul's FLOPs are an implementation's choice, not work the
statement needs, and are not counted."""

from roofline import least_seconds


def read(evidence, args):
    kernel_ms = evidence.metric(args["kernel_metric"])
    if kernel_ms is None or kernel_ms <= 0 or evidence.peak is None:
        return None
    needed = [
        least_seconds(g["module"].columns_read(g.get("params", {})),
                      evidence.device_table, evidence.peak)
        for g in evidence.cell.groups
        if g["module"].columns_read(g.get("params", {})) is not None
    ]
    if not needed:
        return None
    return 100.0 * max(needed) * 1000.0 / kernel_ms
