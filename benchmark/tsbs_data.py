"""TSBS devops cpu-only data, generated from a seed (a copy of the shape of
``horaedb_tpu/tools/tsbs.py::generate_cpu``; nothing here imports the program).

One ``cpu`` table: ``hostname``/``region``/``datacenter`` tags, ten ``usage_*``
fields in [0, 100] following a clipped random walk, one point per host per
10 s. Rows are tick-major, host-minor: row ``t * scale + h`` is host ``h`` at
tick ``t``. The ``World`` keeps the walks as ``(n_ticks, scale)`` arrays, so
the plain references slice a host's series without an index.
"""

from __future__ import annotations

import numpy as np

CPU_FIELDS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest", "usage_guest_nice",
]
TAGS = ["hostname", "region", "datacenter"]
REGIONS = ["us-east-1", "us-west-1", "eu-west-1", "ap-southeast-1"]
INTERVAL_MS = 10_000
HOUR_MS = 3_600_000

CREATE_TABLE = (
    "CREATE TABLE cpu (hostname string TAG, region string TAG, datacenter string TAG, "
    + ", ".join(f"{f} double" for f in CPU_FIELDS)
    + ", ts timestamp NOT NULL, TIMESTAMP KEY(ts)) "
    "ENGINE=Analytic WITH (segment_duration='{segment_duration}')"
)


def host_tags(h: int) -> tuple[str, str, str]:
    region = REGIONS[h % len(REGIONS)]
    return f"host_{h}", region, f"{region}{(h // len(REGIONS)) % 3}"


class World:
    """One deployment's data for one run: what was loaded, and the plain
    answers' raw material."""

    def __init__(self, config: dict, seed: int) -> None:
        self.config = config
        self.seed = int(seed)
        self.scale = int(config["hosts"])
        self.span_ms = int(config["span_ms"])
        self.n_ticks = self.span_ms // INTERVAL_MS
        self.n_rows = self.scale * self.n_ticks
        rng = np.random.default_rng(self.seed)
        self.walks: list[np.ndarray] = []
        for _ in CPU_FIELDS:
            start = rng.uniform(0, 100, self.scale)
            steps = rng.normal(0, 1.0, (self.n_ticks, self.scale))
            np.cumsum(steps, axis=0, out=steps)
            steps += start[None, :]
            self.walks.append(np.clip(steps, 0, 100, out=steps))
        self.tags = [host_tags(h) for h in range(self.scale)]
        self.memo: dict = {}  # statements cache answers that no draw changes

    def window_ms(self, hours: int) -> int:
        """A statement's window of ``hours``, or the loaded span where that is
        shorter."""
        return min(hours * HOUR_MS, self.span_ms)

    # ---- load --------------------------------------------------------------

    def load_columns(self) -> dict[str, np.ndarray]:
        """The loaded rows as columns, in row order."""
        host_ids = np.tile(np.arange(self.scale), self.n_ticks)
        cols: dict[str, np.ndarray] = {}
        for i, tag in enumerate(TAGS):
            values = np.array([t[i] for t in self.tags], dtype=object)
            cols[tag] = values[host_ids]
        cols["ts"] = np.repeat(
            np.arange(self.n_ticks, dtype=np.int64) * INTERVAL_MS, self.scale
        )
        for f, walk in zip(CPU_FIELDS, self.walks):
            cols[f] = walk.reshape(-1)
        return cols

    # ---- the references' raw material ---------------------------------------

    def series(self, field: int, hosts: np.ndarray, tick_lo: int, tick_hi: int) -> np.ndarray:
        """(ticks, hosts) values of one field."""
        return self.walks[field][max(tick_lo, 0):tick_hi][:, np.asarray(hosts)]
