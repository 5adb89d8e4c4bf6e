"""``run.judge`` gives every record the verdict that comparing it on its own
gives, and parses and compares only the distinct bodies of a window. No
server: records built by hand from the statements' own references at the
rehearsal's size."""

import copy
import json

import numpy as np
import pytest

import run
from compare import worst
from tsbs_data import CPU_FIELDS

GROUPED = "cpu-1000x12h.double-groupby-all"
SELECTIVE = "cpu-1000x12h.single-groupby-5-8-1"
SEED = 3_400_000_011


def per_record(evidence):
    """The plain reference of ``judge``: every record parsed and compared on
    its own, as the harness did until PR 34."""
    cell, world = evidence.cell, evidence.world
    numbers, failed = [], 0
    for rec in evidence.records:
        module, params = rec["group"]["module"], rec["group"].get("params", {})
        rec["ok"] = False
        try:
            body = json.loads(rec["body"]) if rec["status"] == 200 else None
        except ValueError:
            body = None
        if body is None:
            numbers.append({"error_responses": 1})
            failed += 1
            continue
        want = module.reference(world, params, rec["ticket"])
        got = module.compare(body.get("rows", body), want, params)
        numbers.append(got)
        rec["ok"] = all(v <= cell.limits[k] for k, v in got.items() if k in cell.limits)
        failed += not rec["ok"]
    numbers.append({"error_responses": 0, "device_served_compared": 0})
    return worst(numbers), failed


class Window:
    """One cell's world and the means to write its answers by hand."""

    def __init__(self, name: str) -> None:
        self.cell = run.Cell(name, rehearse=True)
        self.world = run.World(self.cell.config, SEED)
        self.group = self.cell.groups[0]
        self.module, self.params = self.group["module"], self.group["params"]
        self.rng = np.random.default_rng(SEED)

    def ticket(self):
        return self.module.draw(self.rng, self.world, self.params)[1]

    def rows(self, ticket) -> list[dict]:
        """The reference's answer as the rows a sound program serves."""
        keys, values = self.module.reference(self.world, self.params, ticket)
        if self.module.__name__.endswith("double_groupby_all"):
            key_names, names = ["hostname", "hour"], [f"avg_{f}" for f in CPU_FIELDS]
        else:
            key_names = ["minute"]
            names = [f"max({f})" for f in CPU_FIELDS[:self.params["metrics"]]]
        return [{**dict(zip(key_names, k)), **dict(zip(names, map(float, v)))}
                for k, v in zip(keys, values)]

    def record(self, ticket, body: bytes, status: int = 200) -> dict:
        return {"endpoint": "/sql", "group": self.group, "ticket": ticket, "sql": "SELECT 1",
                "status": status, "body": body, "sent": 0.0, "done": 0.1,
                "wall_sent": 0.0, "wall_done": 0.1}

    def evidence(self, records: list[dict]):
        evidence = run.Evidence(self.cell, self.world, None)
        evidence.records = records
        return evidence


def body_of(rows: list[dict]) -> bytes:
    return json.dumps({"rows": rows}).encode()


def nudged(rows: list[dict]) -> list[dict]:
    out = copy.deepcopy(rows)
    last = list(out[0])[-1]
    out[0][last] *= 1.0 - 1e-3
    return out


def equal_sound(w: Window, n: int):
    ticket = w.ticket()
    return ([w.record(ticket, body_of(w.rows(ticket))) for _ in range(n)],
            {"ok": [True] * n, "compared": 1, "distinct": 1, "numbers": {"wrong_rows": 0}})


def equal_nudged(w: Window, n: int):
    ticket = w.ticket()
    return ([w.record(ticket, body_of(nudged(w.rows(ticket)))) for _ in range(n)],
            {"ok": [False] * n, "compared": 1, "distinct": 1, "numbers": {"wrong_rows": 0},
             "over": "value_gap"})


def equal_dropped_row(w: Window, n: int):
    ticket = w.ticket()
    return ([w.record(ticket, body_of(w.rows(ticket)[:-1])) for _ in range(n)],
            {"ok": [False] * n, "compared": 1, "distinct": 1, "numbers": {"wrong_rows": n}})


def two_bodies_interleaved(w: Window, n: int):
    ticket = w.ticket()
    sound, bad = body_of(w.rows(ticket)), body_of(nudged(w.rows(ticket)))
    return ([w.record(ticket, bad if i % 2 else sound) for i in range(n)],
            {"ok": [True, False] * (n // 2), "compared": 2, "distinct": 2, "over": "value_gap"})


def same_bytes_two_tickets(w: Window, n: int):
    """One ticket's sound answer, sent back under another ticket as well:
    each ticket's reference judges it."""
    one = w.ticket()
    hosts = tuple(h for h in range(w.world.scale) if h not in one[0])[:len(one[0])]
    other = (hosts, *one[1:])
    body = body_of(w.rows(one))
    return ([w.record(other if i % 2 else one, body) for i in range(n)],
            {"ok": [True, False] * (n // 2), "compared": 2, "distinct": 2, "over": "value_gap"})


def errors_among_sound(w: Window, n: int):
    """A shed request, a body cut short and a dropped connection among sound
    answers: an error each time it comes, the cut body parsed once."""
    ticket = w.ticket()
    sound = body_of(w.rows(ticket))
    kinds = [(200, sound), (503, b'{"error": "shed"}'), (200, sound[:-9]), (0, b"OSError()")]
    return ([w.record(ticket, kinds[i % 4][1], kinds[i % 4][0]) for i in range(n)],
            {"ok": [True, False, False, False] * (n // 4), "compared": 1, "distinct": 2,
             "numbers": {"error_responses": 3 * n // 4, "wrong_rows": 0}})


CASES = [
    (GROUPED, equal_sound), (SELECTIVE, equal_sound),
    (GROUPED, equal_nudged), (SELECTIVE, equal_nudged),
    (GROUPED, equal_dropped_row), (SELECTIVE, equal_dropped_row),
    (GROUPED, two_bodies_interleaved), (SELECTIVE, two_bodies_interleaved),
    (SELECTIVE, same_bytes_two_tickets),
    (GROUPED, errors_among_sound), (SELECTIVE, errors_among_sound),
]


@pytest.fixture(scope="module")
def windows():
    return {name: Window(name) for name in (GROUPED, SELECTIVE)}


@pytest.mark.parametrize("workload, build", CASES,
                         ids=[f"{b.__name__}-{w.split('.')[1]}" for w, b in CASES])
def test_judge_gives_each_record_its_own_verdict(windows, monkeypatch, capsys, workload, build):
    w, n = windows[workload], 8
    records, expect = build(w, n)
    want = w.evidence([dict(r) for r in records])
    want_numbers, want_failed = per_record(want)

    parsed, compared = [], []
    monkeypatch.setattr(run, "compare_body",
                        lambda *a, f=run.compare_body: parsed.append(1) or f(*a))
    monkeypatch.setattr(w.module, "compare",
                        lambda *a, f=w.module.compare: compared.append(1) or f(*a))
    got = w.evidence(records)
    numbers, failed = run.judge(got)

    # to the number what comparing each record on its own gives
    assert (numbers, failed) == (want_numbers, want_failed)
    assert [r["ok"] for r in got.records] == [r["ok"] for r in want.records] == expect["ok"]
    assert failed == expect["ok"].count(False)
    assert all(r["body"] is None for r in got.records)
    # for one parse and one comparison a distinct body
    assert (len(parsed), len(compared)) == (expect["distinct"], expect["compared"])
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert (line["phase"], line["answers"], line["distinct"]) == ("judge", n, expect["distinct"])
    # and what the case is there to show
    assert {k: numbers[k] for k in expect.get("numbers", {})} == expect.get("numbers", {})
    if "over" in expect:
        assert numbers[expect["over"]] > w.cell.limits[expect["over"]]
