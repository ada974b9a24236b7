"""A cell of the benchmark: one configuration under one traffic mix.

Everything a cell needs is found by name: the workload entry in
BENCHMARK.json names a configuration (bench/configs/<config>.json) and a
traffic mix (bench/traffic/<traffic>.json), and the configuration's
published `model_type` names the module of its architecture
(bench/arch/<model_type>.py).
Nothing here knows a cell or an architecture by name: a later cell adds
files and entries, and a configuration of an architecture the benchmark
does not run yet adds its module under bench/arch/ as a new file. No file
of the harness is edited for either.
"""
from __future__ import annotations

import dataclasses
import json
import os

from bench import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # the configuration file as committed
    traffic_name: str
    traffic: dict

    @property
    def rows(self) -> int:
        """Rows (sequences) per optimizer update."""
        t = self.traffic
        return int(t["micro_batch"]) * int(t.get("micro_batches_per_update", 1))

    @property
    def tokens_per_step(self) -> int:
        return self.rows * int(self.traffic["seq_len"])


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    w = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load(os.path.join(root, cfg_entry["file"]))
    arch.of(config)  # a model_type that no module implements fails here
    traffic = _load(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
    return Cell(workload, int(w["chips"]), w["config"], config, w["traffic"],
                traffic)


def model_config(cell: Cell):
    """The program's ModelConfig for the cell: the published widths of the
    configuration file, as its architecture's module reads them, and its
    `assumed` model settings."""
    from repro.configs.base import ModelConfig

    kw = arch.of(cell.config).model_fields(cell.config)
    kw.update(cell.config["assumed"]["model"])
    return ModelConfig(name=cell.config_name, **kw)


def train_configs(cell: Cell, seed: int):
    """(RunConfig, TrainConfig) of the program for the cell: one optimizer
    update per step over the traffic's rows, no checkpoints, and the
    loss print only at step 0 (it blocks the host)."""
    from repro.configs.base import GaLoreConfig, TrainConfig
    from repro.launch.train import RunConfig
    from repro.quant.policy import QuantPolicy

    a = cell.config["assumed"]
    t = cell.traffic
    galore = GaLoreConfig(**a["galore"], quant=QuantPolicy(**a["quant"]))
    nm = int(t.get("micro_batches_per_update", 1))
    tc = TrainConfig(galore=galore, seed=seed,
                     microbatch=nm if nm > 1 else 0, **a["train"])
    run = RunConfig(arch=cell.config_name, smoke=False, steps=1 << 30,
                    batch_per_host=cell.rows, seq_len=int(t["seq_len"]),
                    ckpt_dir=os.path.join(ROOT, ".bench_ckpt"), ckpt_every=0,
                    log_every=1 << 30)
    return run, tc
