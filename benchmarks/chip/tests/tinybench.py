"""A benchmark of CPU size for the tests: tiny configurations of both
architectures under tiny traffic, run by the real harness with its
metrics, kernels, references and devices."""
from __future__ import annotations

import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
TINY = Path(__file__).resolve().parent / "tiny"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

CELLS = {"tiny-mha.closed": ("tiny-mha", "tiny-closed"),
         "tiny-gqa.open": ("tiny-gqa", "tiny-open")}


def chip_dir(tmp_path: Path) -> Path:
    d = tmp_path / "chip"
    d.mkdir()
    for sub in ("metrics", "kernels", "references", "devices"):
        (d / sub).symlink_to(CHIP / sub)
    for sub in ("traffic", "checks"):
        (d / sub).symlink_to(TINY / sub)
    return d


def bench() -> dict:
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    return dict(
        real,
        configs=[{"name": c, "file": str(TINY / "configs" / f"{c}.json")}
                 for c in ("tiny-mha", "tiny-gqa")],
        workloads=[{"name": w, "config": c, "traffic": t, "chips": 1}
                   for w, (c, t) in CELLS.items()])
