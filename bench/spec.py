"""Loading a benchmark cell from its files, by name.

``BENCHMARK.json`` at the root lists the cells (``workloads``); each names a
configuration and a traffic mix. Everything that belongs to one of them sits
in a file of its own, found by that name:

    bench/configs/<config>.json     model sizes, as run, with the source
    bench/traffic/<traffic>.json    rule, workers, batch, lr, token law
    bench/limits/<workload>.json    the limits of the numbers ``correct``
                                    compares, with the readings behind them
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/references/<name>.py      a configuration's plain reference

A later cell, mix or metric is new files plus ``BENCHMARK.json`` entries;
no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config_name: str
    config: dict          # bench/configs/<config>.json
    traffic_name: str
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<workload>.json ({} if none yet)
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # metric entries this cell reports with --trace 1
    bench_dir: Path

    @property
    def workers(self) -> int:
        return int(self.traffic["workers"])

    @property
    def global_batch(self) -> int:
        return self.workers * int(self.traffic["seqs_per_worker"])

    @property
    def seq(self) -> int:
        return int(self.traffic["seq"])

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * self.seq


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench_dir: Path | None = None
              ) -> Cell:
    """The workload ``name`` of ``<root>/BENCHMARK.json`` with its files
    from ``bench_dir`` (default ``<root>/bench``). Raises ``KeyError`` for
    an unknown name and ``FileNotFoundError`` for a missing file."""
    spec = _read_json(Path(root) / "BENCHMARK.json")
    bench_dir = Path(bench_dir) if bench_dir else Path(root) / "bench"
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(bench_dir / "configs" / f"{w['config']}.json")
    config = {**config, "source": configs[w["config"]]["source"]}
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    lim_path = bench_dir / "limits" / f"{name}.json"
    limits = _read_json(lim_path) if lim_path.exists() else {}
    e2e = tuple(m for m in spec["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if m["moves"] in reported and _applies(m, name))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                limits=limits, end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


def load_module(path: Path, name: str):
    """Import one file of the benchmark (a metric reader, a reference) by
    path, so that adding one needs no import line anywhere."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(cell: Cell, metric: str):
    """``read(view) -> float | None`` of ``bench/metrics/<metric>.py``."""
    return load_module(cell.bench_dir / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}").read


def reference_model(cell: Cell):
    """The configuration's plain reference, ``bench/references/<name>.py``
    as its file names it."""
    ref = cell.config["reference"]
    return load_module(cell.bench_dir / "references" / f"{ref}.py",
                       f"bench_reference_{ref}")


def peaks(device_kind: str, bench_dir: Path = BENCH) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _read_json(Path(bench_dir) / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
