"""``paged_chunk_attention`` (kernels/paged_chunk_attention.py, MHA/GQA
variant): what one call needs at the least, and its events in a trace.

One call serves one layer of one mixed step.  A row with ``n`` new tokens
after ``p`` cached ones attends query j (position p + j) to keys 0..p+j:

  FLOPs = 4 · Hq · D · sum_j (p + j + 1)        (QK^T and PV, causal)
  bytes = 2 · Hkv · D · (p + n) · b             K and V of the context, read once
        + 2 · Hkv · D · n · b                   the span's K and V, written once
        + 2 · Hq · D · n · b                    q in, attention out

with ``b`` the bytes of one bf16 element.  Padded query rows and the
kernel's page-block rewrite (a whole page read and written per touched
page) are not needed by the algorithm and are not counted.
"""
from __future__ import annotations

# Substrings that name this kernel in a trace event's name or stats.
NAMES = ("paged_chunk_attention", "_mha_kernel")
ITEMSIZE = 2


def matches(name: str, stats: dict) -> bool:
    if "_quant" in name:
        return False
    hay = [name] + [v for v in stats.values() if isinstance(v, str)]
    return any(n in h and "_quant" not in h for h in hay for n in NAMES)


def flops(shapes: dict, rows) -> float:
    """Rows: (p, n, sampled) per row the step advanced."""
    return sum(4.0 * shapes["h"] * shapes["hd"] * (n * p + n * (n + 1) / 2)
               for p, n, _ in rows)


def bytes_moved(shapes: dict, rows) -> float:
    kv = 2 * shapes["hkv"] * shapes["hd"] * ITEMSIZE
    q = 2 * shapes["h"] * shapes["hd"] * ITEMSIZE
    return sum(kv * (p + n) + kv * n + q * n for p, n, _ in rows)
