"""PostgreSQL wire protocol server
(ref: src/server/src/postgresql/service.rs — the reference serves the pg
wire protocol via pgwire on port 5433, config.rs:176-179; this is an
asyncio implementation of protocol 3.0's simple-query flow).

Scope mirrors the reference's shim: startup (SSLRequest answered 'N',
any credentials accepted), simple Query messages with text-format result
rows (every column typed as TEXT), ErrorResponse + ReadyForQuery error
recovery, Terminate.

The extended protocol (Parse/Bind/Describe/Execute/Close/Flush/Sync) is
served with one shim-grade simplification: the statement runs at Bind
time (parameters substituted as SQL literals), so Describe(portal) can
answer with the real RowDescription before Execute streams the rows —
matching what pipelining drivers (psycopg3-style Parse..Sync batches)
expect on the wire. Binary parameter/result formats are refused; all
values travel as text.
"""

from __future__ import annotations

import asyncio
import logging
import re
import struct
from typing import Optional

logger = logging.getLogger("horaedb_tpu.postgres")

DEFAULT_PG_PORT = 5433  # ref: config.rs:176-179

_SSL_REQUEST = 80877103
_CANCEL_REQUEST = 80877102
_TEXT_OID = 25


def _msg(tag: bytes, payload: bytes) -> bytes:
    return tag + (len(payload) + 4).to_bytes(4, "big") + payload


def _cstr(s: str) -> bytes:
    return s.encode("utf-8", "replace") + b"\x00"


_EXTENDED_TAGS = frozenset(b"PBDEHCFdcf")

_PARAM_RE = re.compile(r"\$(\d+)")
_NUMBER_RE = re.compile(r"^-?\d+(\.\d+)?([eE][+-]?\d+)?$")


class _ExtError(Exception):
    """Extended-protocol failure: error the client, discard until Sync."""

    def __init__(self, message: str, sqlstate: str = "XX000") -> None:
        super().__init__(message)
        self.sqlstate = sqlstate


_SET_TIMEOUT_RE = re.compile(
    r"^\s*set\s+(?:session\s+)?statement_timeout\s*(?:=|\s+to)\s*"
    r"'?(\d+)\s*(ms|s|min|h)?'?\s*$",
    re.IGNORECASE,
)

_PG_TIMEOUT_UNITS = {None: 1.0, "ms": 1.0, "s": 1000.0,
                     "min": 60_000.0, "h": 3_600_000.0}


def _pg_timeout_ms(m: "re.Match") -> float:
    """postgres semantics: a bare integer is milliseconds; quoted
    values may carry a unit (ms/s/min/h)."""
    unit = (m.group(2) or "").lower() or None
    return float(m.group(1)) * _PG_TIMEOUT_UNITS[unit]


def _sqlstate_for(extra: dict) -> str:
    """Native SQLSTATE for the gateway's typed errors: shed and quota
    rejections answer 53300 (too_many_connections — class 53,
    insufficient resources: retryable); blocked tables answer 42501
    (insufficient_privilege)."""
    kind = extra.get("kind")
    if kind in ("deadline", "cancelled"):
        # 57014 query_canceled — what postgres answers for both a
        # statement_timeout expiry and pg_cancel_backend
        return "57014"
    if kind in ("overloaded", "quota"):
        return "53300"
    if kind == "blocked":
        return "42501"
    return "XX000"


class _Conn:
    def __init__(self, reader, writer, gateway) -> None:
        self.reader = reader
        self.writer = writer
        self.gateway = gateway
        # extended-protocol state: named prepared statements -> SQL text,
        # named portals -> pre-computed result (see module docstring)
        self._stmts: dict[str, str] = {}
        self._portals: dict[str, tuple] = {}
        self._ext_error = False  # discard extended msgs until Sync
        # per-session time budget (SET statement_timeout = <ms>);
        # None = the server's [limits] query_timeout default
        self._timeout_ms: Optional[float] = None

    async def run(self) -> None:
        if not await self._startup():
            return
        self.writer.write(_msg(b"R", (0).to_bytes(4, "big")))  # AuthenticationOk
        for k, v in (
            ("server_version", "14.0 (horaedb_tpu)"),
            ("client_encoding", "UTF8"),
            ("DateStyle", "ISO"),
        ):
            self.writer.write(_msg(b"S", _cstr(k) + _cstr(v)))
        self.writer.write(_msg(b"K", struct.pack("!II", 1, 0)))  # BackendKeyData
        self._ready()
        await self.writer.drain()
        while True:
            try:
                tag = await self.reader.readexactly(1)
                length = int.from_bytes(await self.reader.readexactly(4), "big")
                body = await self.reader.readexactly(length - 4)
            except (asyncio.IncompleteReadError, ConnectionError):
                return
            if tag == b"X":  # Terminate
                return
            if tag == b"Q":
                await self._query(body.rstrip(b"\x00").decode("utf-8", "replace"))
            elif tag == b"S":  # Sync: leave error state, one ReadyForQuery
                self._ext_error = False
                # implicit transaction ends here: drop portals (named
                # statements survive, matching Postgres portal lifetime)
                self._portals.clear()
                self._ready()
            elif tag[0] in _EXTENDED_TAGS:
                if not self._ext_error:
                    try:
                        await self._extended(tag, body)
                    except _ExtError as e:
                        # per spec: error once, then discard every
                        # extended message until the next Sync
                        self._error(str(e), e.sqlstate)
                        self._ext_error = True
                    except (ValueError, IndexError, struct.error):
                        # truncated/NUL-less body: error, never tear down
                        self._error(f"malformed {tag!r} message")
                        self._ext_error = True
            else:
                self._error(f"unsupported message {tag!r}")
                self._ready()
            await self.writer.drain()

    async def _startup(self) -> bool:
        while True:
            try:
                length = int.from_bytes(await self.reader.readexactly(4), "big")
                body = await self.reader.readexactly(length - 4)
            except (asyncio.IncompleteReadError, ConnectionError):
                return False
            code = int.from_bytes(body[:4], "big")
            if code == _SSL_REQUEST:
                self.writer.write(b"N")  # no TLS; client retries plaintext
                await self.writer.drain()
                continue
            if code == _CANCEL_REQUEST:
                return False
            return True  # StartupMessage (params ignored; any user ok)

    def _ready(self) -> None:
        self.writer.write(_msg(b"Z", b"I"))

    # ---- extended protocol ------------------------------------------------

    async def _extended(self, tag: bytes, body: bytes) -> None:
        if tag == b"P":
            self._parse_msg(body)
        elif tag == b"B":
            await self._bind_msg(body)
        elif tag == b"D":
            await self._describe_msg(body)
        elif tag == b"E":
            self._execute_msg(body)
        elif tag == b"C":
            self._close_msg(body)
        elif tag == b"H":  # Flush — drain happens in the run loop
            pass
        else:
            raise _ExtError(f"unsupported extended message {tag!r}")

    def _parse_msg(self, body: bytes) -> None:
        name, off = _take_cstr(body, 0)
        sql, off = _take_cstr(body, off)
        # declared parameter-type OIDs are accepted and ignored (every
        # parameter is handled as text)
        self._stmts[name] = sql
        self.writer.write(_msg(b"1", b""))  # ParseComplete

    async def _bind_msg(self, body: bytes) -> None:
        portal, off = _take_cstr(body, 0)
        stmt, off = _take_cstr(body, off)
        if stmt not in self._stmts:
            raise _ExtError(f"prepared statement {stmt!r} does not exist")
        nfmt = int.from_bytes(body[off:off + 2], "big"); off += 2
        fmts = []
        for _ in range(nfmt):
            fmts.append(int.from_bytes(body[off:off + 2], "big")); off += 2
        nparams = int.from_bytes(body[off:off + 2], "big"); off += 2
        params: list[Optional[str]] = []
        for i in range(nparams):
            plen = int.from_bytes(body[off:off + 4], "big", signed=True); off += 4
            if plen < 0:
                params.append(None)
                continue
            fmt = fmts[i] if i < len(fmts) else (fmts[0] if len(fmts) == 1 else 0)
            if fmt != 0:
                raise _ExtError("binary parameter format not supported")
            params.append(body[off:off + plen].decode("utf-8", "replace"))
            off += plen
        nrfmt = int.from_bytes(body[off:off + 2], "big"); off += 2
        for i in range(nrfmt):
            if int.from_bytes(body[off:off + 2], "big") != 0:
                raise _ExtError("binary result format not supported")
            off += 2
        sql = _substitute(self._stmts[stmt], params)
        # run now so Describe(portal) can answer with the real row shape
        kind, payload = await self.gateway.execute(
            sql.strip().rstrip(";"), protocol="postgres",
            timeout_ms=self._timeout_ms,
        )
        if kind == "error":
            raise _ExtError(payload[1], _sqlstate_for(payload[2]))
        self._portals[portal] = (kind, payload, sql, 0)  # 0 = row cursor
        self.writer.write(_msg(b"2", b""))  # BindComplete

    async def _describe_msg(self, body: bytes) -> None:
        what = body[:1]
        name, _ = _take_cstr(body, 1)
        if what == b"S":
            if name not in self._stmts:
                raise _ExtError(f"prepared statement {name!r} does not exist")
            sql = self._stmts[name]
            n = _param_count(sql)
            self.writer.write(_msg(
                b"t", n.to_bytes(2, "big") + _TEXT_OID.to_bytes(4, "big") * n
            ))  # ParameterDescription: every parameter is TEXT
            # Drivers in the PQdescribePrepared style (e.g. PgJDBC) rely on
            # this RowDescription as the SELECT's result metadata. The row
            # shape isn't known until Bind, so probe read-only statements
            # with every parameter as NULL and describe what comes back;
            # side-effecting verbs (and probe failures) answer NoData.
            first = sql.lstrip().split(None, 1)
            verb = first[0].lower() if first else ""
            if verb in ("select", "show", "describe", "desc", "explain", "exists"):
                probe = _substitute(sql, [None] * max(n, 0))
                kind, payload = await self.gateway.execute(probe.strip().rstrip(";"))
                if kind == "rows":
                    self._row_description(payload.names)
                    return
            self.writer.write(_msg(b"n", b""))  # NoData
            return
        if name not in self._portals:
            raise _ExtError(f"portal {name!r} does not exist")
        kind, payload, _sql, _pos = self._portals[name]
        if kind == "rows":
            self._row_description(payload.names)
        else:
            self.writer.write(_msg(b"n", b""))  # NoData

    def _execute_msg(self, body: bytes) -> None:
        name, off = _take_cstr(body, 0)
        max_rows = int.from_bytes(body[off:off + 4], "big", signed=True)
        if name not in self._portals:
            raise _ExtError(f"portal {name!r} does not exist")
        kind, payload, sql, pos = self._portals[name]
        if kind == "affected":
            verb = "INSERT 0" if sql.lstrip().lower().startswith("insert") else "OK"
            self.writer.write(_msg(b"C", _cstr(f"{verb} {payload}")))
            return
        names, rows = payload
        # max_rows > 0: emit a slice and suspend the portal; a later
        # Execute on the same portal resumes where this one stopped
        # (cursor-style fetch, per the extended-protocol spec)
        end = len(rows) if max_rows <= 0 else min(pos + max_rows, len(rows))
        for r in rows[pos:end]:
            self._data_row(names, r)
        if end < len(rows):
            self._portals[name] = (kind, payload, sql, end)
            self.writer.write(_msg(b"s", b""))  # PortalSuspended
            return
        self._portals[name] = (kind, payload, sql, end)
        self.writer.write(_msg(b"C", _cstr(f"SELECT {end - pos}")))

    def _close_msg(self, body: bytes) -> None:
        what = body[:1]
        name, _ = _take_cstr(body, 1)
        (self._stmts if what == b"S" else self._portals).pop(name, None)
        self.writer.write(_msg(b"3", b""))  # CloseComplete

    def _error(self, message: str, sqlstate: str = "XX000") -> None:
        payload = (
            b"S" + _cstr("ERROR") + b"C" + _cstr(sqlstate)
            + b"M" + _cstr(message) + b"\x00"
        )
        self.writer.write(_msg(b"E", payload))

    async def _query(self, sql: str) -> None:
        q = sql.strip().rstrip(";")
        if not q:
            self.writer.write(_msg(b"I", b""))  # EmptyQueryResponse
            self._ready()
            return
        lowered = q.lower()
        word = lowered.split()[0] if lowered.split() else ""
        if word in ("set", "begin", "start", "commit", "rollback"):
            # session time budget (the postgres knob): SET
            # statement_timeout = <ms> applies to every later statement
            # on this connection; 0 restores the server default. Other
            # SETs stay swallowed chatter.
            m_timeout = _SET_TIMEOUT_RE.match(q)
            if m_timeout is not None:
                ms = _pg_timeout_ms(m_timeout)
                self._timeout_ms = ms if ms > 0 else None
            tag = {"set": "SET", "begin": "BEGIN", "start": "BEGIN",
                   "commit": "COMMIT", "rollback": "ROLLBACK"}[word]
            self.writer.write(_msg(b"C", _cstr(tag)))
            self._ready()
            return
        # The shared gateway applies routing, fences, limiter, metrics —
        # including the per-protocol latency labelset.
        kind, payload = await self.gateway.execute(
            q, protocol="postgres", timeout_ms=self._timeout_ms
        )
        if kind == "error":
            _status, msg, extra = payload
            self._error(msg, _sqlstate_for(extra))
            self._ready()
            return
        if kind == "affected":
            verb = "INSERT 0" if "insert" in lowered[:10] else "OK"
            self.writer.write(_msg(b"C", _cstr(f"{verb} {payload}")))
            self._ready()
            return
        names, rows = payload
        self._row_description(names)
        for r in rows:
            self._data_row(names, r)
        self.writer.write(_msg(b"C", _cstr(f"SELECT {len(rows)}")))
        self._ready()

    def _row_description(self, names) -> None:
        desc = len(names).to_bytes(2, "big")
        for name in names:
            desc += _cstr(name) + struct.pack("!IhIhih", 0, 0, _TEXT_OID, -1, -1, 0)
        self.writer.write(_msg(b"T", desc))

    def _data_row(self, names, r: dict) -> None:
        payload = len(names).to_bytes(2, "big")
        for n in names:
            v = r.get(n)
            if v is None:
                payload += (-1).to_bytes(4, "big", signed=True)
            else:
                b = _render(v).encode("utf-8", "replace")
                payload += len(b).to_bytes(4, "big") + b
        self.writer.write(_msg(b"D", payload))


def _take_cstr(body: bytes, off: int) -> tuple[str, int]:
    end = body.index(b"\x00", off)
    return body[off:end].decode("utf-8", "replace"), end + 1


def _scan_params(sql: str):
    """Yield (start, end, n) for each $n placeholder OUTSIDE string
    literals — real Postgres never treats ``'$1'`` text as a parameter.
    The dialect's only literal syntax is ``'...'`` with ``''`` escaping
    (no backslash escapes — see query/parser.py tokenizer)."""
    i, n = 0, len(sql)
    while i < n:
        c = sql[i]
        if c == "'":
            i += 1
            while i < n:
                if sql[i] == "'":
                    if i + 1 < n and sql[i + 1] == "'":
                        i += 2  # escaped quote, still in the literal
                        continue
                    i += 1
                    break
                i += 1
            continue
        if c == "$":
            m = _PARAM_RE.match(sql, i)
            if m:
                yield m.start(), m.end(), int(m.group(1))
                i = m.end()
                continue
        i += 1


def _param_count(sql: str) -> int:
    # one ParameterDescription entry per $1..$max, like real Postgres
    return max((n for _, _, n in _scan_params(sql)), default=0)


def _substitute(sql: str, params: list) -> str:
    """Inline $n text parameters as SQL literals (numbers raw, everything
    else single-quoted with '' escaping, NULL for missing values)."""
    out = []
    last = 0
    for start, end, num in _scan_params(sql):
        idx = num - 1
        if idx < 0 or idx >= len(params):
            raise _ExtError(f"no value supplied for parameter ${num}")
        v = params[idx]
        if v is None:
            lit = "NULL"
        elif _NUMBER_RE.match(v):
            lit = v
        else:
            lit = "'" + v.replace("'", "''") + "'"
        out.append(sql[last:start])
        out.append(lit)
        last = end
    out.append(sql[last:])
    return "".join(out)


def _render(v) -> str:
    if isinstance(v, bool):
        return "t" if v else "f"
    if isinstance(v, float):
        return repr(v)
    return str(v)


class PostgresServer:
    def __init__(self, gateway, host: str = "127.0.0.1", port: int = DEFAULT_PG_PORT):
        self.gateway = gateway
        self.host = host
        self.port = port
        self._server: Optional[asyncio.AbstractServer] = None

    async def start(self) -> None:
        async def handle(reader, writer):
            try:
                await _Conn(reader, writer, self.gateway).run()
            except (asyncio.IncompleteReadError, ConnectionError):
                pass
            except Exception:
                logger.exception("postgres session failed")
            finally:
                writer.close()

        self._server = await asyncio.start_server(handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        logger.info("postgres protocol on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
