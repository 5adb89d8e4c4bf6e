"""A query result as the body of a ``/sql`` answer, from its columns.

``SqlAnswer(names, columns, nulls).body()`` is the UTF-8 bytes of
``{"rows": [{name: value, ...}, ...], "names": [...]}``, made without a
Python object per value or per row: each column becomes an Arrow array of
JSON texts (``join_rows``), the rows are joined element-wise, and the
joined array's data buffer is the body (``body_of_joined``).
``column_values`` is the per-column conversion to Python values that
``ResultSet.to_pylist`` and the encoder's fallback share, so what a value
looks like is decided in one place.

The parsed answer is ``json.dumps(to_pylist(), default=json_default)``'s:
the same keys in the same order, every value the same JSON type and the
same double or integer. The bytes differ only in the exponent form of a
double where Arrow and ``repr`` differ (``1e+15`` for
``1000000000000000.0``, ``0.00001`` for ``1e-05``): the same double.
"""

from __future__ import annotations

import json
from typing import Any, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..common_types.dict_column import DictColumn
from ..utils.metrics import REGISTRY

# Which route encoded an answer. Eager registration: every label reads 0
# from the first scrape.
_ENCODES = {
    route: REGISTRY.counter(
        "horaedb_response_encode_total",
        "/sql answers encoded to a body (vectorised: every column through "
        "Arrow; per_value: a column formatted per value, or an answer under "
        "the size rule; pylist: dict rows from a forward)",
        labels={"route": route},
    )
    for route in ("vectorised", "per_value", "pylist")
}


def json_default(v: Any):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    raise TypeError(f"not JSON serializable: {type(v)}")


def dumps(obj: Any) -> str:
    return json.dumps(obj, default=json_default)


def column_values(col, null_mask=None) -> list:
    """One column as Python values: numpy scalars unwrapped (an f32 reads
    as the double of its value), ``None`` where the mask is set."""
    if isinstance(col, DictColumn):
        col = col.decode()
    col = np.asarray(col)
    vals = col.tolist()
    if col.dtype == object:
        vals = [v.item() if isinstance(v, np.generic) else v for v in vals]
    if null_mask is not None:
        for i in np.flatnonzero(null_mask).tolist():
            vals[i] = None
    return vals


def rows_as_dicts(names: Sequence[str], columns: Sequence, nulls=None) -> list[dict]:
    if not columns:
        return []
    nulls = nulls or {}
    per_col = [column_values(c, nulls.get(n)) for n, c in zip(names, columns)]
    return [dict(zip(names, row)) for row in zip(*per_col)]


# An answer of fewer rows than this is encoded per value (the column-wise
# rows, then ``json.dumps``): the vectorised route costs 30-40 us a column
# whatever the size. Scratch timings on this repo's host CPU (Python 3.12.12,
# pyarrow 25.0.0; a str, an int64 and float64 columns; medians of 300, ms,
# vectorised / per value): 12 columns at 1 row 0.58 / 0.029, 48 rows 0.86 /
# 0.46, 64 rows 0.66 / 0.64, 96 rows 0.68 / 1.29, 1,000 rows 2.4 / 13.1,
# 12,000 rows 22.5 / 118.6; 6 columns cross between 64 and 96 rows too, 24
# columns between 48 and 64, 2 columns between 128 and 256: the crossover
# lies in the rows, not in rows x columns, since both routes pay per column.
SMALL_ANSWER_ROWS = 64


def _str_texts(values: list, codes) -> Optional[pa.Array]:
    """JSON texts of a column of ``str`` given as distinct values and codes:
    ``json.dumps`` once per distinct value, ASCII-escaped."""
    if not all(isinstance(v, str) for v in values):
        return None
    return pa.array([json.dumps(v) for v in values], pa.string()).take(codes)


def _texts(col, null_mask) -> Optional[list]:
    """One column as the parts of its JSON texts (Arrow string arrays that
    are concatenated per row; a null part reads ``null``), or None for a
    column of a kind this route does not know."""
    valid = None
    if null_mask is not None:
        null_mask = np.asarray(null_mask, dtype=bool)
        if null_mask.any():
            valid = pa.array(~null_mask)

    def masked(texts: pa.Array) -> pa.Array:
        if valid is None:
            return texts
        return pc.if_else(valid, texts, pa.scalar(None, texts.type))

    if isinstance(col, DictColumn):
        texts = _str_texts(np.asarray(col.values).tolist(), pa.array(col.codes))
        return None if texts is None else [masked(texts)]
    col = np.asarray(col)
    kind = col.dtype.kind
    if col.ndim != 1:
        return None
    if kind in "OU":
        # (Arrow cuts a fixed-width numpy string at its first NUL)
        arr = pa.array(col.astype(object) if kind == "U" else col)
        if not pa.types.is_string(arr.type) or arr.null_count:
            return None
        coded = arr.dictionary_encode()
        texts = _str_texts(coded.dictionary.to_pylist(), coded.indices)
        return None if texts is None else [masked(texts)]
    if kind in "biu":
        return [masked(pc.cast(pa.array(col), pa.string()))]
    if kind != "f" or col.dtype.itemsize < 4:
        return None
    # an f32 answers as the double of its value, as ``.item()`` gave it
    col = col.astype(np.float64, copy=False)
    texts = pc.cast(pa.array(col), pa.string())
    finite = np.isfinite(col)
    if not finite.all():
        # as json.dumps spells them
        for where, word in (
            (np.isnan(col), "NaN"),
            (np.isposinf(col), "Infinity"),
            (np.isneginf(col), "-Infinity"),
        ):
            texts = pc.if_else(pa.array(where), word, texts)
    # Arrow writes an integral double bare (15, -0), which a client would
    # read back as an integer: it keeps its ".0", unless the text is in
    # exponent form (1e+15), which reads back as a double as it stands.
    integral = finite & (col == np.trunc(col))
    if null_mask is not None:
        integral &= ~null_mask
    if not integral.any():
        return [masked(texts)]
    bare = pc.and_(pa.array(integral), pc.invert(pc.match_substring(texts, "e")))
    return [masked(texts), pc.if_else(bare, ".0", "")]


def join_rows(names: Sequence[str], columns: Sequence, nulls=None):
    """-> (the rows' JSON texts as one Arrow string array, each ending in the
    separator to the next; the route): ``vectorised`` when every column went
    through Arrow, ``per_value`` when one was formatted value by value. Needs
    a row at least."""
    nulls = nulls or {}
    # a name given twice is one key of a row: the first's place and the
    # last's value, as a dict filled in column order has it
    by_name = dict(zip(names, columns))
    route = "vectorised"
    parts: list = []
    for i, (name, col) in enumerate(by_name.items()):
        parts.append(("{" if i == 0 else ", ") + json.dumps(name) + ": ")
        mask = nulls.get(name)
        try:
            texts = _texts(col, mask)
        except (pa.ArrowException, TypeError, ValueError, OverflowError):
            texts = None
        if texts is None:
            route = "per_value"
            texts = [
                pa.array(
                    [json.dumps(v, default=json_default) for v in column_values(col, mask)],
                    pa.string(),
                )
            ]
        parts.extend(texts)
    parts.append("}, ")
    joined = pc.binary_join_element_wise(
        *parts, "", null_handling="replace", null_replacement="null"
    )
    return joined, route


def body_of_joined(joined: pa.Array, names: Sequence[str]) -> bytes:
    """The body around ``join_rows``' array: its data buffer is the rows'
    text, less the last row's separator."""
    offsets = np.frombuffer(joined.buffers()[1], dtype=np.int32)
    lo = int(offsets[joined.offset])
    hi = int(offsets[joined.offset + len(joined)]) - len(", ")
    tail = '], "names": ' + json.dumps(list(names)) + "}"
    return b"".join(
        (b'{"rows": [', memoryview(joined.buffers()[2])[lo:hi], tail.encode())
    )


class SqlAnswer:
    """The rows of a statement as ``SqlGateway.execute`` hands them to a wire
    handler: the names, and either face on demand — ``body()``, the bytes of
    the ``/sql`` answer (HTTP), or ``rows()``, dict rows (MySQL, PostgreSQL).
    Each is made once, so a coalesced twin gets its leader's. Holds a
    result's ``columns`` and ``nulls`` or, for an answer forwarded from
    another node, the dict ``rows`` parsed from its JSON."""

    def __init__(self, names, columns=None, nulls=None, rows: Optional[list] = None) -> None:
        self.names = list(names)
        self.route = None if rows is None else "pylist"
        self._columns = columns
        self._nulls = nulls
        self._rows = rows
        self._joined = None
        self._body: Optional[bytes] = None

    @property
    def num_rows(self) -> int:
        if self._rows is not None:
            return len(self._rows)
        return len(self._columns[0]) if self._columns else 0

    def __iter__(self):
        """``names, rows = answer``: the payload's older form."""
        yield self.names
        yield self.rows()

    def rows(self) -> list[dict]:
        if self._rows is None:
            self._rows = rows_as_dicts(self.names, self._columns, self._nulls)
        return self._rows

    def prepare(self) -> None:
        """The per-value part of the JSON face, which the gateway runs on the
        worker thread that ran the statement: the columns' texts joined into
        rows or, under the size rule, the dict rows."""
        if self.route is not None:
            return
        if self.num_rows >= max(SMALL_ANSWER_ROWS, 1):
            try:
                self._joined, self.route = join_rows(
                    self.names, self._columns, self._nulls
                )
                return
            except pa.ArrowCapacityError:
                pass  # more text than one Arrow string array holds (2 GiB)
        self.rows()
        self.route = "per_value"

    def body(self) -> bytes:
        if self._body is None:
            self.prepare()
            if self._joined is not None:
                self._body = body_of_joined(self._joined, self.names)
                self._joined = None
            else:
                self._body = dumps({"rows": self._rows, "names": self.names}).encode()
            _ENCODES[self.route].inc()
        return self._body
