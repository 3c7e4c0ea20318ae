"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

  configs/<config>.json       sizes as run, engine settings, reference name
  traffic/<traffic>.json      parameters of the one traffic generator
  checks/<workload>.json      limits of the numbers ``correct`` compares
  metrics/<metric>.py         reader: ``read(ctx) -> float | None``
  kernels/<kernel>.py         a kernel's operations, bytes and trace matcher
  references/<name>.py        plain reference of an architecture
  devices/<device_kind>.json  peaks of one device kind (non-name chars -> _)
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]     # benchmarks/chip
ROOT = CHIP_DIR.parents[1]                           # the checkout


class SpecError(RuntimeError):
    """A file the cell needs is missing or malformed."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def load_module(path: Path):
    """Import one reader/kernel/reference file by path (names may hold
    '-' and '.', so they are not importable as packages)."""
    path = Path(path)
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_file(kind: str, chip_dir: Path = CHIP_DIR) -> Path:
    return chip_dir / "devices" / (re.sub(r"[^A-Za-z0-9_.-]", "_", kind)
                                   + ".json")


class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names."""

    def __init__(self, workload: str, bench: dict | None = None,
                 chip_dir: Path = CHIP_DIR, root: Path = ROOT):
        self.chip_dir = Path(chip_dir)
        self.root = Path(root)
        self.bench = (bench if bench is not None
                      else load_json(self.root / "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SpecError(f"unknown workload {workload!r}; "
                            f"have {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(self.root / self.config_entry["file"])
        self.traffic = load_json(self.chip_dir / "traffic"
                                 / f"{self.workload['traffic']}.json")
        self.check = load_json(self.chip_dir / "checks" / f"{workload}.json")
        self.chips = int(self.workload["chips"])

    def metrics(self, trace: bool) -> list[dict]:
        """This cell's end-to-end metrics (``trace`` off) or per-layer
        metrics (``trace`` on), in ``BENCHMARK.json`` order."""
        e2e = [m for m in self.bench["end_to_end"]
               if self.name in m.get("workloads", [self.name])]
        if not trace:
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def reader(self, metric: str):
        return load_module(self.chip_dir / "metrics" / f"{metric}.py")

    def kernel(self, name: str):
        return load_module(self.chip_dir / "kernels" / f"{name}.py")

    def reference(self):
        return load_module(self.chip_dir / "references"
                           / f"{self.config['reference']}.py")

    def device(self, kind: str) -> dict:
        path = device_file(kind, self.chip_dir)
        if not path.is_file():
            raise SpecError(f"no peaks for device kind {kind!r} ({path})")
        return load_json(path)
