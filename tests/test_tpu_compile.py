"""TPU v5e compiles of the serving kernels at real widths, without a chip.

Each test compiles for one chip of a described (not attached) ``v5e:2x2``
topology with ``interpret=False`` and checks that the program holds the
Mosaic kernel (``tpu_custom_call``).  Shapes are olmo-1b's (16 MHA heads,
head_dim 128, bf16 pools, page 64, batch 8) and deepseek-v2-lite's latent
width for MLA; the quantized twins use page 128, because their f32 scale
rows are one page wide and a TPU row is 128 lanes.  Mosaic refuses what
interpret mode accepts (unaligned slices, oversized VMEM), so these guard
every change to the kernels at no chip time.

The topology is described inside a module fixture and never at import
time: only one process may load the TPU library, and the test workers
import every test file.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, H, D, PS, P, MAXP, C = 8, 16, 128, 64, 256, 32, 64
QPS = 128
MLA_R, MLA_RD, MLA_DP = 512, 64, 640


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to a persistent cache
        # but cannot be read back without one: keep the cache off.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def test_paged_chunk_attention_compiles_for_v5e(spec):
    from repro.kernels import paged_chunk_attention as pc
    pool = spec((P, H, PS, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, kp, vp, bt, st, sp, kn, vn: pc.paged_chunk_attention(
            q, kp, vp, bt, st, sp, kn, vn, scale=D ** -0.5),
        spec((B, H, C, D), jnp.bfloat16), pool, pool,
        spec((B, MAXP), jnp.int32), spec((B,), jnp.int32),
        spec((B,), jnp.int32), spec((B, H, C, D), jnp.bfloat16),
        spec((B, H, C, D), jnp.bfloat16))
    assert _has_kernel(compiled)


def test_paged_decode_attention_compiles_for_v5e(spec):
    from repro.kernels import paged_decode_attention as pd
    pool = spec((P, H, PS, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, kp, vp, bt, pos, kn, vn: pd.paged_decode_attention(
            q, kp, vp, bt, pos, kn, vn, scale=D ** -0.5),
        spec((B, H, D), jnp.bfloat16), pool, pool,
        spec((B, MAXP), jnp.int32), spec((B,), jnp.int32),
        spec((B, H, D), jnp.bfloat16), spec((B, H, D), jnp.bfloat16))
    assert _has_kernel(compiled)


def test_page_transfer_compiles_for_v5e(spec):
    from repro.kernels import page_transfer as pt
    pool = spec((P, H, PS, D), jnp.bfloat16)
    compiled = _compile(
        lambda s, d, si, di: pt.page_transfer(s, d, si, di), pool, pool,
        spec((16,), jnp.int32), spec((16,), jnp.int32))
    assert _has_kernel(compiled)


def test_paged_mla_kernels_compile_for_v5e(spec):
    from repro.kernels import paged_chunk_attention as pc
    from repro.kernels import paged_mla_decode as pm
    pool = spec((P, PS, MLA_DP), jnp.bfloat16)
    bt, vec = spec((B, MAXP), jnp.int32), spec((B,), jnp.int32)
    width = MLA_R + MLA_RD
    chunk = _compile(
        lambda q, lp, bt, st, sp, ln: pc.paged_mla_chunk(
            q, lp, bt, st, sp, ln, r=MLA_R, scale=0.1),
        spec((B, H, C, width), jnp.float32), pool, bt, vec, vec,
        spec((B, C, MLA_DP), jnp.bfloat16))
    decode = _compile(
        lambda q, lp, bt, pos, ln: pm.paged_mla_decode(
            q, lp, bt, pos, ln, r=MLA_R, scale=0.1),
        spec((B, H, width), jnp.float32), pool, bt, vec,
        spec((B, MLA_DP), jnp.bfloat16))
    assert _has_kernel(chunk) and _has_kernel(decode)


@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
def test_quantized_mha_kernels_compile_for_v5e(spec, qdtype):
    from repro.kernels import paged_chunk_attention as pc
    from repro.kernels import paged_decode_attention as pd
    pool, scales = spec((P, H, QPS, D), qdtype), spec((P, H, QPS), jnp.float32)
    bt, vec = spec((B, MAXP), jnp.int32), spec((B,), jnp.int32)
    chunk = _compile(
        lambda q, kp, ks, vp, vs, bt, st, sp, kn, vn:
        pc.paged_chunk_attention_quant(q, kp, ks, vp, vs, bt, st, sp, kn, vn,
                                       scale=0.1, qmax=127.0),
        spec((B, H, C, D), jnp.bfloat16), pool, scales, pool, scales, bt,
        vec, vec, spec((B, H, C, D), jnp.float32),
        spec((B, H, C, D), jnp.float32))
    decode = _compile(
        lambda q, kp, ks, vp, vs, bt, pos, kn, vn:
        pd.paged_decode_attention_quant(q, kp, ks, vp, vs, bt, pos, kn, vn,
                                        scale=0.1, qmax=127.0),
        spec((B, H, D), jnp.bfloat16), pool, scales, pool, scales, bt, vec,
        spec((B, H, D), jnp.float32), spec((B, H, D), jnp.float32))
    assert _has_kernel(chunk) and _has_kernel(decode)


@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
def test_quantized_mla_kernels_compile_for_v5e(spec, qdtype):
    from repro.kernels import paged_chunk_attention as pc
    from repro.kernels import paged_mla_decode as pm
    pool, scales = spec((P, QPS, MLA_DP), qdtype), spec((P, QPS), jnp.float32)
    bt, vec = spec((B, MAXP), jnp.int32), spec((B,), jnp.int32)
    width = MLA_R + MLA_RD
    chunk = _compile(
        lambda q, lp, ls, bt, st, sp, ln: pc.paged_mla_chunk_quant(
            q, lp, ls, bt, st, sp, ln, r=MLA_R, scale=0.1, qmax=127.0),
        spec((B, H, C, width), jnp.float32), pool, scales, bt, vec, vec,
        spec((B, C, MLA_DP), jnp.float32))
    decode = _compile(
        lambda q, lp, ls, bt, pos, ln: pm.paged_mla_decode_quant(
            q, lp, ls, bt, pos, ln, r=MLA_R, scale=0.1, qmax=127.0),
        spec((B, H, width), jnp.float32), pool, scales, bt, vec,
        spec((B, MLA_DP), jnp.float32))
    assert _has_kernel(chunk) and _has_kernel(decode)


def _served_step_compiled(one_chip, monkeypatch, make, width, *extra):
    """Compile a served-step constructor, platform-resolved to the kernels,
    at olmo-1b's published widths with depth cut to 2 layers."""
    import repro.configs as configs
    from repro.kernels import ops
    from repro.models import lm

    # The described chip is not the default backend; steer the platform
    # choice the engine makes to the one the chip would make.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.default_impl() == "pallas"
    cfg = dataclasses.replace(configs.get("olmo-1b"), num_layers=2)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shaped(lm.abstract_params(cfg))
    cache = shaped(jax.eval_shape(lambda: lm.init_cache(
        cfg, B, 2048, paged=True, page_size=PS)))
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    step = jax.jit(make(cfg), donate_argnums=(1,))
    return step.lower(
        params, cache, jax.ShapeDtypeStruct((B, width), jnp.int32,
                                            sharding=one_chip),
        vec, vec, *shaped(extra)).compile()


@pytest.mark.parametrize("width", [1, 128])
def test_mixed_step_compiles_for_v5e_at_olmo_widths(one_chip, monkeypatch,
                                                    width):
    from repro.serving import engine as engine_mod
    compiled = _served_step_compiled(
        one_chip, monkeypatch, engine_mod.make_mixed_step_fn, width,
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert _has_kernel(compiled)


def test_verify_step_compiles_for_v5e_at_olmo_widths(one_chip, monkeypatch):
    """The speculative verify step: all-logits spans of 1 + 4 drafts."""
    from repro.serving import engine as engine_mod
    compiled = _served_step_compiled(
        one_chip, monkeypatch, engine_mod.make_verify_step_fn, 5)
    assert _has_kernel(compiled)
