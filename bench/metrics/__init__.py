"""Per-layer metrics: one reader per file, found by the metric's name.

`bench/metrics/<name>.py` defines `read(ctx) -> float | None` for the
per-layer metric `<name>` of BENCHMARK.json. A reader that finds nothing to
read returns None, and the run leaves the metric out of its line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Context:
    """What a reader may read: the cell, the peak table's row for the
    device, the run's readings (`run["trace"]` holds the reduced trace) and
    the number of chips."""
    cell: object
    peaks: dict
    run: dict
    n_chips: int

    @property
    def trace(self) -> dict:
        return self.run.get("trace") or {}

    @property
    def steps(self) -> int:
        return self.run["steps"]


def load_reader(name: str):
    path = os.path.join(HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
