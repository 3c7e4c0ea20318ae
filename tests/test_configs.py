"""Config registry: exact assigned hyperparameters + shape applicability."""
from __future__ import annotations

import pytest

import repro.configs as configs
from repro.configs.shapes import SHAPES, applicable

ASSIGNED = {
    # name: (layers, d_model, heads, kv, d_ff, vocab)
    "xlstm-125m": (12, 768, 4, 4, 0, 50304),
    "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
    "granite-34b": (88, 6144, 48, 1, 24576, 49152),
    "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
    "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
    "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
    "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
    "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
    "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
}


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_exact_assigned_config(name):
    c = configs.get(name)
    want = ASSIGNED[name]
    got = (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
           c.vocab_size)
    assert got == want, f"{name}: {got} != {want}"


def test_moe_configs():
    for name in ("deepseek-moe-16b", "deepseek-v2-lite-16b"):
        m = configs.get(name).moe
        assert (m.num_experts, m.top_k, m.num_shared) == (64, 6, 2)
    assert configs.get("deepseek-v2-lite-16b").mla.kv_lora_rank == 512


def test_frontend_stubs():
    assert configs.get("paligemma-3b").num_prefix_tokens == 256
    enc = configs.get("whisper-tiny").encoder
    assert enc is not None and enc.seq_len == 1500


def test_recurrentgemma_pattern():
    c = configs.get("recurrentgemma-2b")
    assert c.block_pattern == ("rglru", "rglru", "local")
    assert c.window == 2048
    assert c.tail_blocks == ("rglru", "rglru")      # 26 = 8*3 + 2


def test_applicability_matrix():
    long = SHAPES["long_500k"]
    runs = {n for n in configs.ARCHS
            if applicable(configs.get(n), long)[0]}
    assert runs == {"xlstm-125m", "recurrentgemma-2b"}
    # Every arch runs every other shape.
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        for n in configs.ARCHS:
            ok, _ = applicable(configs.get(n), SHAPES[shape])
            assert ok


def test_reduced_preserves_family():
    for n in configs.ARCHS:
        full = configs.get(n)
        red = configs.reduced(full)
        assert red.block_pattern == full.block_pattern
        assert (red.moe is None) == (full.moe is None)
        assert (red.mla is None) == (full.mla is None)
        assert (red.encoder is None) == (full.encoder is None)
        assert red.param_count() < full.param_count() / 50
