"""``paged_mla_chunk`` (kernels/paged_chunk_attention.py, latent variant):
what one call needs at the least, and its events in a trace.

One call serves one MLA layer of one mixed step.  A row with ``n`` new
tokens after ``p`` cached ones attends query j (position p + j) to latent
rows 0..p+j with absorbed queries of width r + rope per head:

  FLOPs = 2 · H · (r + rope) · ctx + 2 · H · r · ctx   per live query,
          ctx = p + j + 1 (scores against [c; k_pe], then P · c)
  bytes = Dp · (p + n) · b        the latent context read once
        + Dp · n · b              the span's latent rows written
        + H · (r + rope) · n · 4  q in (float32, absorbed)
        + H · r · n · 4           ctx out (float32)

with ``Dp`` the pool's row width, r + rope padded to a multiple of 128
lanes (576 -> 640 for DeepSeek-V2-Lite), and ``b`` the bytes of one bf16
element.  Padded query rows and the kernel's page rewrite are not counted.

``ctx.shapes`` carries dense widths only, so the latent widths come from
the configuration file of the cell that ``paged_mla_chunk_roofline``
lists first in ``BENCHMARK.json``.
"""
from __future__ import annotations

from harness import spec

METRIC = "paged_mla_chunk_roofline"
NAMES = ("paged_mla_chunk", "_mla_kernel")
ITEMSIZE = 2


def widths(bench: dict | None = None) -> dict:
    bench = bench or spec.load_json(spec.ROOT / "BENCHMARK.json")
    metric = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    cfg = spec.Cell(metric["workloads"][0], bench).config
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {"h": cfg["num_attention_heads"], "r": r, "rope": rope,
            "dp": -(-(r + rope) // 128) * 128,
            "layers": cfg["num_hidden_layers"]}


def matches(name: str, stats: dict) -> bool:
    hay = [name] + [v for v in stats.values() if isinstance(v, str)]
    return any(n in h and "_quant" not in h for h in hay for n in NAMES)


def flops(w: dict, rows) -> float:
    """Rows: (p, n, sampled) per row the step advanced."""
    per = 2.0 * w["h"] * (2 * w["r"] + w["rope"])
    return sum(per * (n * p + n * (n + 1) / 2) for p, n, _ in rows)


def bytes_moved(w: dict, rows) -> float:
    lat = w["dp"] * ITEMSIZE
    q = w["h"] * (w["r"] + w["rope"]) * 4 + w["h"] * w["r"] * 4
    return sum(lat * (p + n) + lat * n + q * n for p, n, _ in rows)
