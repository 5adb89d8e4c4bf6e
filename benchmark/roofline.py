"""What a statement needs of the chip, defined by the statement and not by a
kernel: for each resident column it reads, the column's stored bytes scaled to
the logical rows inside the statement's window (never the padded length), over
the chip's HBM bandwidth."""

from __future__ import annotations


def least_bytes(columns_read, device_table: list[dict]) -> float:
    """``columns_read`` = (names of resident columns, share of the loaded span
    the statement's window covers); ``device_table`` = the column rows of
    ``system.public.device`` (column_name, bytes, logical_rows)."""
    names, share = columns_read
    by_name = {r["column_name"]: r for r in device_table}
    total = 0.0
    for name in names:
        row = by_name.get(name)
        if row is None:
            continue  # not resident: the statement's scan never read it
        logical = row["logical_rows"]
        padded = 1 << max(logical - 1, 0).bit_length()
        total += row["bytes"] * logical / padded * share
    return total


def least_seconds(columns_read, device_table: list[dict], peak: dict) -> float:
    return least_bytes(columns_read, device_table) / peak["hbm_bytes_per_s"]
