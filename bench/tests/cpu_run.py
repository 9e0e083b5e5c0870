"""A whole run at smoke size on the CPU, past the harness's look for a
chip.  With ``exact`` the engine serves float32, so a sound program
matches the reference to rounding."""
from __future__ import annotations

from bench import harness
from bench.tests import smoke

SEED = 2 ** 33 + 11
SECONDS = 5.0


def run(cell_name: str, monkeypatch=None, exact: bool = True, bench=None,
        cfg=None, mix=None, seconds: float = SECONDS) -> dict:
    b, c, m = smoke.cell(cell_name)
    b, c, m = bench or b, cfg or c, mix or m
    if exact:
        c["deployment"]["dtype"] = "float32"
    return harness.run_cell(cell_name, SEED, seconds, False, bench=b,
                            cfg=c, mix=m, platform="cpu")
