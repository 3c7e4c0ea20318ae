"""``moe_grouped_matmul`` (kernels/moe_grouped_matmul.py): the grouped
SwiGLU of the routed experts this chip holds; what one call needs at the
least, and its events in a trace.

One call serves one expert layer of one mixed step.  With L live
positions in the step (every row's span), ``k`` experts per token and
``held`` of ``E`` experts here, the expected live pairs are
P = L · k · held / E, and:

  FLOPs = 6 · d · d_expert · P        gate, up and down of each pair
  bytes = held · 3 · d · d_expert · b every held expert's weights, once
        + 2 · d · P · b               the pairs' activations in and out

with ``b`` the bytes of one bf16 element.  The weights of a held expert
that no pair chose are not read, so the count is high by those: an expert
is empty with probability (1 - k/E)^L, 4.3% of the held experts at L = 32
(a step of 32 decoding rows, k = 6, E = 64) and under 0.2% from L = 64.
The routing-dependent spread of P about its expectation is not counted.

``ctx.shapes`` carries dense widths only, so the expert widths come from
the configuration file of the cell that ``moe_grouped_matmul_roofline``
lists first in ``BENCHMARK.json``.
"""
from __future__ import annotations

from harness import spec

METRIC = "moe_grouped_matmul_roofline"
NAMES = ("moe_grouped_matmul",)
ITEMSIZE = 2


def widths(bench: dict | None = None) -> dict:
    bench = bench or spec.load_json(spec.ROOT / "BENCHMARK.json")
    metric = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    cfg = spec.Cell(metric["workloads"][0], bench).config
    return {"d": cfg["hidden_size"], "dx": cfg["moe_intermediate_size"],
            "k": cfg["num_experts_per_tok"],
            "held": cfg["n_routed_experts"],
            "experts": cfg["published"]["n_routed_experts"],
            "layers": (cfg["num_hidden_layers"]
                       - cfg["first_k_dense_replace"])}


def matches(name: str, stats: dict) -> bool:
    hay = [name] + [v for v in stats.values() if isinstance(v, str)]
    return any(n in h for h in hay for n in NAMES)


def pairs(w: dict, rows) -> float:
    """Expected live pairs of one step's rows (p, n, sampled)."""
    live = sum(n for _, n, _ in rows)
    return live * w["k"] * w["held"] / w["experts"]


def flops(w: dict, rows) -> float:
    return 6.0 * w["d"] * w["dx"] * pairs(w, rows)


def bytes_moved(w: dict, rows) -> float:
    weights = w["held"] * 3 * w["d"] * w["dx"] * ITEMSIZE
    return weights + 2 * w["d"] * pairs(w, rows) * ITEMSIZE
