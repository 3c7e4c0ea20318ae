"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh) cell:
    compute term    = flops_per_device / PEAK_FLOPS_BF16
    memory term     = bytes_per_device / HBM_BW
    collective term = collective_bytes_per_device / ICI_BW
(all per-device quantities from the dry-run's extrapolated cost analysis —
per-device-time formulation; equivalent to the global/chips form).

Reports the dominant term (the bottleneck), the MODEL_FLOPS/HLO ratio
(useful-compute fraction — catches remat/dispatch waste), the roofline
fraction (model-flops-time / bound-time), and a one-line "what would move
the dominant term down".
"""
from __future__ import annotations

import json
from pathlib import Path

PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9 * 4          # ~4 usable links per v5e chip (2D torus)

DRYRUN_DIR = Path(__file__).resolve().parent.parent / "experiments" / "dryrun"


def load_cells(mesh: str = "single", variant: str = "baseline") -> list[dict]:
    d = DRYRUN_DIR / mesh
    if not d.exists():
        raise FileNotFoundError(f"run launch/dryrun.py first ({d})")
    return [json.loads(f.read_text())
            for f in sorted(d.glob(f"*__{variant}.json"))]


def roofline(cell: dict) -> dict | None:
    if cell.get("status") != "ok":
        return None
    t_comp = cell["flops_per_device"] / PEAK_FLOPS_BF16
    t_mem = cell["bytes_per_device"] / HBM_BW
    t_coll = (cell["collective_bytes_per_device"].get("total", 0.0)) / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    model_flops = cell.get("model_flops_est", 0.0)
    n_dev = cell["n_devices"]
    hlo_global = cell["flops_per_device"] * n_dev
    useful = model_flops / hlo_global if hlo_global else 0.0
    # Roofline fraction: time the model's useful flops WOULD take at peak,
    # over the bound (dominant) time — "how close to roofline the step is".
    t_useful = (model_flops / n_dev) / PEAK_FLOPS_BF16
    bound = max(terms.values())
    frac = t_useful / bound if bound > 0 else 0.0
    # Memory-roofline fraction (decode/serving): a decode step MUST stream
    # the persistent state (params + cache) once; useful_bytes/HLO_bytes is
    # the fair closeness metric for memory-bound cells (the compute-peak
    # fraction is structurally tiny for decode).
    mem_frac = None
    ma = cell.get("memory_analytic")
    if ma and cell.get("kind") == "decode":
        useful_bytes = ma.get("params_per_device", 0) + ma.get(
            "cache_per_device", 0)
        if cell["bytes_per_device"] > 0:
            mem_frac = useful_bytes / cell["bytes_per_device"]
    return dict(cell, t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
                dominant=dominant, useful_ratio=useful,
                roofline_fraction=frac, memory_roofline_fraction=mem_frac)


_ADVICE = {
    "compute": "cut non-useful FLOPs: remat policy, MoE dispatch tightness, "
               "fused attention (no score materialization)",
    "memory": "cut HBM traffic: bf16/quantized KV, windowed cache, fusion, "
              "larger per-step batch to amortize weight streaming",
    "collective": "cut bytes on ICI: pmax-packed coordination merge, "
                  "reduce-scatter instead of all-gather, overlap, "
                  "lower sync cadence",
}


def table(mesh: str = "single", variant: str = "baseline") -> list[str]:
    rows = []
    hdr = (f"{'arch':24s} {'shape':12s} {'t_comp(s)':>10s} {'t_mem(s)':>10s} "
           f"{'t_coll(s)':>10s} {'domin':>6s} {'MODEL/HLO':>9s} {'frac':>6s}")
    rows.append(hdr)
    for cell in load_cells(mesh, variant):
        if cell.get("status") == "skipped":
            rows.append(f"{cell['arch']:24s} {cell['shape']:12s} "
                        f"{'N/A — ' + cell['reason']}")
            continue
        r = roofline(cell)
        if r is None:
            rows.append(f"{cell['arch']:24s} {cell['shape']:12s} ERROR "
                        f"{cell.get('error', '')[:60]}")
            continue
        mf = (f" memfrac={r['memory_roofline_fraction']:.3f}"
              if r.get("memory_roofline_fraction") is not None else "")
        rows.append(
            f"{r['arch']:24s} {r['shape']:12s} {r['t_compute']:10.3e} "
            f"{r['t_memory']:10.3e} {r['t_collective']:10.3e} "
            f"{r['dominant']:>6s} {r['useful_ratio']:9.3f} "
            f"{r['roofline_fraction']:6.3f}{mf}")
    return rows


def summary_rows(mesh: str = "single", variant: str = "baseline") -> list[str]:
    """CSV rows for benchmarks/run.py."""
    out = []
    for cell in load_cells(mesh, variant):
        r = roofline(cell)
        if r is None:
            continue
        out.append(
            f"roofline/{r['arch']}/{r['shape']},{r['t_compute'] * 1e6:.2f},"
            f"dom={r['dominant']} t_mem={r['t_memory']:.2e}s "
            f"t_coll={r['t_collective']:.2e}s frac={r['roofline_fraction']:.3f} "
            f"advice={_ADVICE[r['dominant']][:40]}")
    return out


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()
    for row in table(args.mesh, args.variant):
        print(row)


if __name__ == "__main__":
    main()
