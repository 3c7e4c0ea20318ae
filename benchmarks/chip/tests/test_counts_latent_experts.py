"""The latent chunk and grouped expert kernels' FLOP and byte counts agree
with a hand count of a small case, and their widths come from the
configuration of the cell their metric lists."""
import importlib.util
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]


def load(rel):
    spec = importlib.util.spec_from_file_location(rel.replace("/", "_"),
                                                  CHIP / rel)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


# One row: 3 latent rows cached, 2 new (positions 3 and 4).
ROWS = [(3, 2, True)]


def test_mla_chunk_counts_by_hand():
    k = load("kernels/paged_mla_chunk.py")
    w = {"h": 2, "r": 4, "rope": 2, "dp": 128, "layers": 1}
    # Query 3 sees 4 latent rows, query 4 sees 5: 9 pairs, each
    # 2 * (r + rope) for the scores and 2 * r for P.c, in 2 heads.
    assert k.flops(w, ROWS) == 9 * 2 * (2 * 6 + 2 * 4)
    lat = 128 * 2                      # one padded bf16 pool row
    q = 2 * 6 * 4 + 2 * 4 * 4          # f32 q in and ctx out per token
    assert k.bytes_moved(w, ROWS) == 5 * lat + 2 * lat + 2 * q


def test_moe_counts_by_hand():
    k = load("kernels/moe_grouped_matmul.py")
    w = {"d": 8, "dx": 4, "k": 2, "held": 2, "experts": 8, "layers": 1}
    rows = [(3, 2, True), (10, 6, False)]        # 8 live positions
    assert k.pairs(w, rows) == 8 * 2 * 2 / 8 == 4
    assert k.flops(w, rows) == 6 * 8 * 4 * 4
    assert k.bytes_moved(w, rows) == 2 * 3 * 8 * 4 * 2 + 2 * 8 * 4 * 2


def test_widths_come_from_the_listed_cell():
    mla = load("kernels/paged_mla_chunk.py").widths()
    assert (mla["h"], mla["r"], mla["rope"], mla["dp"], mla["layers"]) == \
        (16, 512, 64, 640, 27)
    moe = load("kernels/moe_grouped_matmul.py").widths()
    assert moe == {"d": 2048, "dx": 1408, "k": 6, "held": 16,
                   "experts": 64, "layers": 26}


def test_matchers():
    mla = load("kernels/paged_mla_chunk.py")
    assert mla.matches("paged_mla_chunk.3", {})
    assert not mla.matches("paged_mla_chunk_quant.1", {})
    assert not mla.matches("paged_chunk_attention.7", {})
    moe = load("kernels/moe_grouped_matmul.py")
    assert moe.matches("moe_grouped_matmul.2", {})
    assert not moe.matches("fusion.12", {"long_name": "dot_general"})
