"""The benchmark's own tests: run them with ``python -m pytest benchmark/tests``
on the CPU. They are not part of the repo's tier-1 suite."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def rehearse(capsys):
    """Drive the rest of a run past the harness's look for a chip: the CPU
    rehearsal of one cell, in this process. -> the result line."""
    import run

    def go(workload: str, seed: int = 2_400_000_011, seconds: float = 2.0) -> dict:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0", "--rehearse"])
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go
