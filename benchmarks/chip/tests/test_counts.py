"""The FLOP and byte counts agree with a hand count of a small case."""
import importlib.util
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]


def load(rel):
    spec = importlib.util.spec_from_file_location(rel.replace("/", "_"),
                                                  CHIP / rel)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


SHAPES = {"layers": 2, "d": 8, "h": 2, "hkv": 1, "hd": 4, "ff": 16,
          "v": 10, "glu": True}
# One row: 3 tokens cached, 2 new (positions 3 and 4), a token sampled.
ROWS = [(3, 2, True)]


def test_chunk_attention_flops_by_hand():
    k = load("kernels/paged_chunk_attention.py")
    # Query 3 sees keys 0..3, query 4 keys 0..4: 9 pairs, each 2*D for
    # QK^T and 2*D for PV in each of the 2 query heads.
    assert k.flops(SHAPES, ROWS) == 9 * 4 * 4 * 2


def test_chunk_attention_bytes_by_hand():
    k = load("kernels/paged_chunk_attention.py")
    kv_pos = 2 * 1 * 4 * 2            # K and V, 1 KV head, D 4, bf16
    q_tok = 2 * 2 * 4 * 2             # q in and out, 2 heads, D 4, bf16
    want = 5 * kv_pos + 2 * kv_pos + 2 * q_tok
    assert k.bytes_moved(SHAPES, ROWS) == want == 176


def test_chunk_attention_matcher():
    k = load("kernels/paged_chunk_attention.py")
    assert k.matches("paged_chunk_attention.5", {})
    assert k.matches("custom-call.3", {"long_name": "x/_mha_kernel/y"})
    assert not k.matches("fusion.7", {"long_name": "dot_general"})
    assert not k.matches("paged_chunk_attention_quant.1", {})


def test_step_flops_by_hand():
    m = load("metrics/step_mfu.py")
    per_layer = 2 * 8 * 2 * 4 + 2 * 8 * 1 * 4 + 3 * 8 * 16
    assert m.layer_params(SHAPES) == per_layer == 576
    dense = 2 * 2 * 576 * 2           # 2 tokens, 2 layers
    attn = 4 * 2 * 2 * 4 * 9          # 2 layers, 9 query-key pairs
    head = 2 * 8 * 10                 # one sampled row
    assert m.step_flops(SHAPES, ROWS) == dense + attn + head
    assert m.step_flops(SHAPES, [(3, 2, False)]) == dense + attn
