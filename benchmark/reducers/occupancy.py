"""Resident column bytes of ``system.public.device`` per logical row."""


def read(evidence, args):
    rows = max((r["logical_rows"] for r in evidence.device_table), default=0)
    if rows <= 0:
        return None
    return sum(r["bytes"] for r in evidence.device_table) / rows
