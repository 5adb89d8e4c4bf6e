"""The ``/sql`` answer encoded from a result's columns (query/result_json):
its parsed body is what ``json.dumps`` of the old per-value ``to_pylist``
parsed to, with types, on both routes of the size rule; the column-wise
``to_pylist`` is the old one's list; coalesced twins share one encode."""

from __future__ import annotations

import asyncio
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from horaedb_tpu.common_types.dict_column import DictColumn
from horaedb_tpu.query import result_json
from horaedb_tpu.query.executor import ResultSet
from horaedb_tpu.query.result_json import SqlAnswer, json_default


def old_to_pylist(names, columns, nulls=None):
    """``ResultSet.to_pylist`` as it was before the columns were encoded:
    the plain reference."""
    out = []
    nulls = nulls or {}
    for i in range(len(columns[0]) if columns else 0):
        row = {}
        for name, col in zip(names, columns):
            m = nulls.get(name)
            if m is not None and m[i]:
                row[name] = None
            else:
                v = col[i]
                row[name] = v.item() if isinstance(v, np.generic) else v
        out.append(row)
    return out


def old_body(names, columns, nulls=None):
    return json.dumps(
        {"rows": old_to_pylist(names, columns, nulls), "names": list(names)},
        default=json_default,
    )


def same(a, b) -> bool:
    """Equal with types: 15.0 is not 15, True is not 1, -0.0 is not 0.0,
    NaN sits where NaN sat, keys come in the same order."""
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        return a == b and math.copysign(1.0, a) == math.copysign(1.0, b)
    if isinstance(a, list):
        return len(a) == len(b) and all(same(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return list(a) == list(b) and all(same(a[k], b[k]) for k in a)
    return a == b


DOUBLES = np.array([
    15.0, -15.0, 0.0, -0.0, 100.0, 123456.0, 2.0**53, -(2.0**63),
    5e-324, 2.2250738585072014e-308, -4.9e-324,           # subnormals, tiny
    1e15, 1e16, 1e17, 999999999999999.0, 1.2345678901234568e17, 1e21, 1e22,
    1e-4, 1e-5, 1e-6, 1e-7, 2.5e-5, 0.000123456,
    0.1, 1.0 / 3.0, 1.7976931348623157e308, -1.7976931348623157e308,
    float(np.float32(0.1)), float(np.float32(16777217.0)),
    np.nan, np.inf, -np.inf,
])
STRINGS = [
    "plain", "", 'quo"te', "back\\slash", "tab\there", "nl\nnl", "\x00\x1f",
    "café", "日本語", "\U0001f600 astral", "</script>", "host_7",
]
INT_KINDS = ["int8", "int16", "int32", "int64", "uint8", "uint16", "uint32", "uint64"]


def _ints(dtype):
    info = np.iinfo(dtype)
    return np.array([info.min, info.max, 0, 1, 6, info.max // 3], dtype=dtype)


def _case_str(n=None):
    vals = STRINGS if n is None else [STRINGS[i % len(STRINGS)] for i in range(n)]
    return np.array(vals, dtype=object)


def _cases():
    cases = {}
    for kind in INT_KINDS:
        cases[kind] = (["v"], [_ints(kind)], None)
    cases["bool"] = (["b"], [np.array([True, False, True])], None)
    cases["float64"] = (["d"], [DOUBLES], None)
    f32 = np.array([0.1, 1.5, 16777216.0, 3.4028235e38, 1e-45, -0.0, np.nan,
                    np.inf, 15.0, 1e-5, 1e15], dtype=np.float32)
    cases["float32"] = (["f"], [f32], None)
    cases["float16"] = (["h"], [np.array([0.1, 2.0], dtype=np.float16)], None)
    cases["object_str"] = (["s"], [_case_str()], None)
    cases["unicode_dtype"] = (["u"], [np.array(STRINGS)], None)
    cases["lone_surrogate"] = (["s"], [np.array(["ok", "\ud800"], dtype=object)], None)
    cases["object_mixed"] = (
        ["m"],
        [np.array(["a", 1, 2.5, None, True, np.int64(7), np.float32(0.1),
                   np.bool_(False), 15.0], dtype=object)],
        None,
    )
    cases["object_bytes"] = (
        ["b"], [np.array([b"ab", b"\xff\xfe", "str"], dtype=object)], None,
    )
    cases["object_none"] = (["n"], [np.array(["a", None, "b"], dtype=object)], None)
    cases["object_numbers"] = (["o"], [np.array([1, 2**70, -3], dtype=object)], None)
    cases["dict_column"] = (
        ["h", "v"],
        [DictColumn(np.array([2, 0, 1, 2], dtype=np.int32),
                    np.array(['a"', "bé", "c"], dtype=object)),
         np.arange(4.0)],
        None,
    )
    cases["dict_column_of_numbers"] = (
        ["h"],
        [DictColumn(np.array([1, 0], dtype=np.int32), np.array([1.5, 2], dtype=object))],
        None,
    )
    n = len(DOUBLES)
    mask = np.arange(n) % 3 == 0
    cases["null_masks"] = (
        ["s", "i", "d", "b", "u64"],
        [_case_str(n), np.arange(n, dtype=np.int64) - 5, DOUBLES,
         np.arange(n) % 2 == 0, np.arange(n, dtype=np.uint64) + np.uint64(2**63)],
        {"s": mask, "i": ~mask, "d": mask, "b": np.ones(n, bool),
         "u64": np.zeros(n, bool)},
    )
    cases["null_mask_on_fallback"] = (
        ["m"], [np.array(["a", 1, None], dtype=object)],
        {"m": np.array([False, True, False])},
    )
    cases["zero_rows"] = (["a", "b"], [np.empty(0), np.empty(0, dtype=object)], None)
    cases["empty_result"] = (["a", "b"], ResultSet.empty(["a", "b"]).columns, None)
    cases["one_row"] = (
        ["s", "i", "d"],
        [np.array(["x"], dtype=object), np.array([6]), np.array([15.0])], None,
    )
    cases["zero_columns"] = ([], [], None)
    cases["name_twice"] = (
        ["a", "b", "a"], [np.array([1, 2]), np.array([1.0, 2.5]),
                          np.array(["x", "y"], dtype=object)], None,
    )
    cases["names_escaped"] = (
        ['we"ird', "café", "avg(usage_user)"],
        [np.array([1]), np.array([2.0]), np.array(["z"], dtype=object)], None,
    )
    rng = np.random.default_rng(31)
    rows = 300
    cases["grouped_answer"] = (
        ["hostname", "hour"] + [f"avg_{i}" for i in range(10)],
        [np.array([f"host_{i // 12}" for i in range(rows)], dtype=object),
         (np.arange(rows) % 12 * 3_600_000).astype(np.int64)]
        + [rng.random(rows) * 100 for _ in range(9)]
        + [np.round(rng.random(rows) * 4) * 25.0],
        None,
    )
    cases["random_bits"] = (
        ["d"],
        [rng.integers(0, 2**64, 4000, dtype=np.uint64).view(np.float64)], None,
    )
    return cases


CASES = _cases()
ROUTES = {"vectorised": 0, "per_value": 10**9}


@pytest.mark.parametrize("forced", list(ROUTES))
@pytest.mark.parametrize("case", list(CASES))
def test_body_parses_to_the_old_answer(case, forced, monkeypatch):
    monkeypatch.setattr(result_json, "SMALL_ANSWER_ROWS", ROUTES[forced])
    names, columns, nulls = CASES[case]
    want = json.loads(old_body(names, columns, nulls))
    answer = SqlAnswer(names, columns, nulls)
    body = answer.body()
    assert isinstance(body, bytes)
    got = json.loads(body)
    assert same(got, want), (got, want)
    assert answer.route in ("vectorised", "per_value")
    if forced == "per_value" or not want["rows"]:
        assert answer.route == "per_value"


@pytest.mark.parametrize("case", list(CASES))
def test_columnwise_to_pylist_is_the_old_list(case):
    names, columns, nulls = CASES[case]
    got = ResultSet(list(names), list(columns), nulls).to_pylist()
    assert same(got, old_to_pylist(names, columns, nulls))


@pytest.mark.parametrize("case,route", [
    ("float64", "vectorised"), ("float32", "vectorised"), ("int64", "vectorised"),
    ("uint64", "vectorised"), ("bool", "vectorised"), ("object_str", "vectorised"),
    ("unicode_dtype", "vectorised"), ("dict_column", "vectorised"),
    ("null_masks", "vectorised"), ("grouped_answer", "vectorised"),
    ("name_twice", "vectorised"),
    ("object_mixed", "per_value"), ("object_bytes", "per_value"),
    ("object_none", "per_value"), ("object_numbers", "per_value"),
    ("float16", "per_value"), ("lone_surrogate", "per_value"),
    ("dict_column_of_numbers", "per_value"),
])
def test_which_columns_the_vectorised_route_knows(case, route, monkeypatch):
    monkeypatch.setattr(result_json, "SMALL_ANSWER_ROWS", 0)
    answer = SqlAnswer(*CASES[case])
    answer.body()
    assert answer.route == route


def test_doubles_keep_their_spelling_where_python_and_arrow_agree(monkeypatch):
    """An integral double keeps its ``.0``, the specials read as json.dumps
    writes them, and only the exponent form may differ in the bytes."""
    monkeypatch.setattr(result_json, "SMALL_ANSWER_ROWS", 0)
    col = np.array([15.0, -0.0, 0.5, np.nan, np.inf, -np.inf, 1e15, 1e-5, 1e22])
    body = SqlAnswer(["d"], [col]).body().decode()
    texts = re.findall(r'\{"d": ([^}]*)\}', body)
    assert texts == ["15.0", "-0.0", "0.5", "NaN", "Infinity", "-Infinity",
                     "1e+15", "0.00001", "1e+22"]
    assert body.startswith('{"rows": [{"d": 15.0}, {"d": -0.0}, ')
    assert body.endswith('{"d": 1e+22}], "names": ["d"]}')


def test_datetime_is_refused_as_before(monkeypatch):
    col = np.array(["2026-01-01T00:00:00"], dtype="datetime64[ms]")
    with pytest.raises(TypeError):
        old_body(["t"], [col])
    for rows in ROUTES.values():
        monkeypatch.setattr(result_json, "SMALL_ANSWER_ROWS", rows)
        with pytest.raises(TypeError):
            SqlAnswer(["t"], [col]).body()


@pytest.mark.parametrize("rows,route", [
    (1, "per_value"), (result_json.SMALL_ANSWER_ROWS - 1, "per_value"),
    (result_json.SMALL_ANSWER_ROWS, "vectorised"), (1000, "vectorised"),
])
def test_size_rule_picks_the_route_and_both_agree(rows, route):
    names = ["s", "i", "d"]
    columns = [_case_str(rows), np.arange(rows), np.arange(rows) / 4.0]
    answer = SqlAnswer(names, columns)
    body = answer.body()
    assert answer.route == route
    assert same(json.loads(body), json.loads(old_body(names, columns)))


def test_forwarded_dict_rows_encode_as_before():
    rows = [{"h": "a", "v": 1.5}, {"h": "b", "v": None}]
    answer = SqlAnswer(["h", "v"], rows=rows)
    names, got = answer
    assert (names, got) == (["h", "v"], rows) and answer.rows() is rows
    assert answer.body() == json.dumps({"rows": rows, "names": ["h", "v"]}).encode()
    assert answer.route == "pylist"


def test_either_face_is_made_once():
    names, columns, nulls = CASES["grouped_answer"]
    answer = SqlAnswer(names, columns, nulls)
    assert answer.num_rows == 300
    assert answer.body() is answer.body()
    assert answer.rows() is answer.rows()
    assert same(answer.rows(), old_to_pylist(names, columns, nulls))


ENCODE_LINES = {
    route: f'horaedb_response_encode_total{{route="{route}"}}'
    for route in ("vectorised", "per_value", "pylist")
}


def _encodes(text: str) -> dict:
    return {
        route: float(next(
            ln for ln in text.splitlines() if ln.startswith(line)
        ).split()[-1])
        for route, line in ENCODE_LINES.items()
    }


def test_counter_exports_zero_from_process_start():
    out = subprocess.run(
        [sys.executable, "-c",
         "import horaedb_tpu.server.http\n"
         "from horaedb_tpu.utils.metrics import REGISTRY\n"
         "print(REGISTRY.expose())"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    assert _encodes(out.stdout) == {"vectorised": 0.0, "per_value": 0.0, "pylist": 0.0}


def test_rows_span_names_the_route_on_the_http_wire_only():
    from horaedb_tpu.server.http import _answer_of
    from horaedb_tpu.utils import tracectx

    names, columns, nulls = CASES["grouped_answer"]
    result = ResultSet(list(names), list(columns), nulls)
    trace, handle = tracectx.start_trace(31, "http_sql")
    try:
        with tracectx.span("handle"):
            http = _answer_of(result, "http")
            mysql = _answer_of(result, "mysql")
    finally:
        tracectx.finish_trace(handle, record=False)
    (handle_span,) = trace.root.children
    (rows_span,) = handle_span.children
    assert rows_span.name == "rows"
    assert rows_span.attrs == {"rows": 300, "route": "vectorised"}
    assert http.route == "vectorised" and mysql.route is None
    assert same(mysql.rows(), old_to_pylist(names, columns, nulls))


@pytest.fixture()
def wide_db():
    import horaedb_tpu

    conn = horaedb_tpu.connect(None)
    conn.execute(
        "CREATE TABLE enc (host string TAG, v double, n bigint, "
        "ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    values = ", ".join(
        f"('h{i % 7}', {i / 4.0}, {i}, {1000 + i})" for i in range(200)
    )
    conn.execute(f"INSERT INTO enc (host, v, n, ts) VALUES {values}")
    yield conn
    conn.close()


def test_coalesced_twins_share_one_encoded_body(wide_db):
    """Two identical in-flight /sql reads: one execution, one encode, and
    byte-equal bodies from the one ``SqlAnswer``."""
    import threading

    from aiohttp.test_utils import TestClient, TestServer

    from horaedb_tpu.server import create_app
    from horaedb_tpu.utils.metrics import REGISTRY

    sql = "SELECT host, v, n, ts FROM enc ORDER BY ts"
    gate = threading.Event()
    calls = []

    async def body():
        app = create_app(wide_db)
        proxy = app["proxy"]
        orig = type(proxy).handle_sql

        def slow_handle(self_, q):
            if q == sql:
                calls.append(q)
                gate.wait(5)
            return orig(self_, q)

        proxy.handle_sql = slow_handle.__get__(proxy)
        async with TestClient(TestServer(app)) as client:
            before = _encodes(REGISTRY.expose())
            posts = [
                asyncio.ensure_future(client.post("/sql", json={"query": sql}))
                for _ in range(2)
            ]
            await asyncio.sleep(0.3)  # both enter; one leader executes
            gate.set()
            resps = await asyncio.gather(*posts)
            bodies = [await r.read() for r in resps]
            after = _encodes(REGISTRY.expose())
            kinds = [r.headers["Content-Type"] for r in resps]
        return bodies, kinds, {r: after[r] - before[r] for r in after}

    bodies, kinds, gained = asyncio.run(body())
    assert len(calls) == 1, calls
    assert bodies[0] == bodies[1]
    assert kinds == ["application/json; charset=utf-8"] * 2
    assert gained == {"vectorised": 1.0, "per_value": 0.0, "pylist": 0.0}
    rows = json.loads(bodies[0])["rows"]
    assert len(rows) == 200 and rows[6] == {"host": "h6", "v": 1.5, "n": 6, "ts": 1006}
    assert type(rows[4]["v"]) is float and type(rows[4]["n"]) is int


FIELDS = [f"usage_{k}" for k in ("user", "system", "idle", "nice", "iowait")]
HOSTS, TICKS = 24, 3 * 60  # 24 hosts x 3 h at a point a minute
STATEMENTS = {
    "double_groupby_all": (
        "SELECT hostname, time_bucket(ts, '1h') AS hour, "
        + ", ".join(f"avg({f}) AS avg_{f}" for f in FIELDS)
        + " FROM cpu WHERE ts >= 0 AND ts < 10800000 "
        "GROUP BY hostname, time_bucket(ts, '1h') ORDER BY hostname, hour",
        HOSTS * 3, "vectorised",
    ),
    "single_groupby": (
        "SELECT time_bucket(ts, '1m') AS minute, "
        + ", ".join(f"max({f})" for f in FIELDS)
        + " FROM cpu WHERE ts >= 600000 AND ts < 4200000 AND hostname IN "
        "('host_3', 'host_7', 'host_11') GROUP BY time_bucket(ts, '1m') "
        "ORDER BY minute",
        60, "per_value",
    ),
    "high_cpu_count_max": (
        "SELECT count(*) AS c, max(usage_user) AS peak FROM cpu "
        "WHERE usage_user > 90 AND ts >= 0 AND ts < 10800000",
        1, "per_value",
    ),
}


@pytest.fixture(scope="module")
def cpu_db():
    import horaedb_tpu
    from horaedb_tpu.common_types import RowGroup
    from horaedb_tpu.common_types.schema import compute_tsid

    conn = horaedb_tpu.connect(None)
    conn.execute(
        "CREATE TABLE cpu (hostname string TAG, "
        + ", ".join(f"{f} double" for f in FIELDS)
        + ", ts timestamp NOT NULL, TIMESTAMP KEY(ts)) ENGINE=Analytic"
    )
    table = conn.catalog.open("cpu")
    rng = np.random.default_rng(31)
    host_ids = np.tile(np.arange(HOSTS), TICKS)
    columns = {
        "hostname": np.array([f"host_{h}" for h in range(HOSTS)], dtype=object)[host_ids],
        "ts": np.repeat(np.arange(TICKS, dtype=np.int64) * 60_000, HOSTS),
    }
    for f in FIELDS:  # whole numbers among them: averages and peaks that end in .0
        columns[f] = np.round(rng.uniform(0, 100, HOSTS * TICKS) * 2) / 2
    columns["tsid"] = compute_tsid([columns["hostname"]])
    table.write(RowGroup(table.schema, columns))
    yield conn
    conn.close()


@pytest.mark.parametrize("statement", list(STATEMENTS))
def test_served_answer_of_a_cell_statement_is_the_old_one(statement, cpu_db):
    """The benchmark's three statement shapes over ``/sql``: the served bytes
    parse, with types, to what ``json.dumps`` of the old rows parsed to, by
    the route the size rule gives each."""
    from aiohttp.test_utils import TestClient, TestServer

    from horaedb_tpu.server import create_app
    from horaedb_tpu.utils.metrics import REGISTRY

    sql, n_rows, route = STATEMENTS[statement]

    async def body():
        async with TestClient(TestServer(create_app(cpu_db))) as client:
            before = _encodes(REGISTRY.expose())
            resp = await client.post("/sql", json={"query": sql})
            assert resp.status == 200
            after = _encodes(REGISTRY.expose())
            return await resp.read(), {r: after[r] - before[r] for r in after}

    served, gained = asyncio.run(body())
    out = cpu_db.execute(sql)
    assert out.num_rows == n_rows
    assert same(json.loads(served), json.loads(old_body(out.names, out.columns, out.nulls)))
    assert gained == {r: float(r == route) for r in gained}


def test_an_answer_past_arrow_capacity_is_encoded_per_value(monkeypatch):
    """Rows of more text than one Arrow string array holds (2 GiB, where
    ``binary_join_element_wise`` raises): the answer is not refused."""
    import pyarrow as pa

    def full(*_a, **_k):
        raise pa.ArrowCapacityError("array cannot contain more than 2147483646 bytes")

    monkeypatch.setattr(result_json, "join_rows", full)
    names, columns, nulls = CASES["grouped_answer"]
    answer = SqlAnswer(names, columns, nulls)
    assert same(json.loads(answer.body()), json.loads(old_body(names, columns, nulls)))
    assert answer.route == "per_value"
