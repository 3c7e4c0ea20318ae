"""TPU v5e compiles of the serving kernels at real widths, without a chip.

Each test compiles for one chip of a described (not attached) ``v5e:2x2``
topology with ``interpret=False`` and checks that the program holds the
Mosaic kernel (``tpu_custom_call``).  Shapes are olmo-1b's (16 MHA heads,
head_dim 128, bf16 pools, page 64, batch 8) and deepseek-v2-lite's latent
width for MLA; the chunk kernels also compile at each served cell's shapes
(olmo-1b, starcoder2-15b and deepseek-v2-lite, batch 32), and so does
deepseek-v2-lite's grouped expert kernel.  The chunk kernels take
``[L, P, ...]`` pool stacks and a layer index.  The quantized twins use
page 128, because their f32 scale rows are one page wide and a TPU row is
128 lanes.
Mosaic refuses what interpret mode accepts (unaligned slices, oversized
VMEM), so these guard every change to the kernels at no chip time.

The topology is described inside a module fixture and never at import
time: only one process may load the TPU library, and the test workers
import every test file.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, H, D, PS, P, MAXP, C = 8, 16, 128, 64, 256, 32, 64
L = 3                   # layers of the pool stacks the chunk kernels take
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


def _kernel_ops(compiled) -> list[str]:
    """Instruction names of the Mosaic kernels in ``compiled``: the names
    their events carry in a profiler trace."""
    return [re.match(r"\s*(?:ROOT )?%([\w.-]+) = ", line).group(1)
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.fixture
def spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def test_paged_chunk_attention_compiles_for_v5e(spec):
    from repro.kernels import paged_chunk_attention as pc
    pool = spec((L, P, H, PS, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, kp, vp, bt, st, sp, kn, vn, lyr: pc.paged_chunk_attention(
            q, kp, vp, bt, st, sp, kn, vn, layer=lyr, scale=D ** -0.5),
        spec((B, H, C, D), jnp.bfloat16), pool, pool,
        spec((B, MAXP), jnp.int32), spec((B,), jnp.int32),
        spec((B,), jnp.int32), spec((B, H, C, D), jnp.bfloat16),
        spec((B, H, C, D), jnp.bfloat16), spec((1,), jnp.int32))
    assert _has_kernel(compiled)


# (Hq, Hkv, maxp, pool pages, window) of the served cells: olmo-1b (MHA,
# context 2048) and starcoder2-15b (GQA group 12, context 4096, its
# published window), batch 32.
SERVED = {"olmo-1b": (16, 16, 32, 640, None),
          "starcoder2-15b": (48, 4, 64, 2049, 4096)}


@pytest.mark.parametrize("c", [1, 32])
@pytest.mark.parametrize("arch", sorted(SERVED))
def test_paged_chunk_attention_compiles_for_v5e_at_served_shapes(
        spec, arch, c):
    """The block walk at each served cell's shapes and at both ends of its
    widths: one Mosaic kernel, and its event keeps the name the
    benchmark's matcher sums."""
    from repro.kernels import paged_chunk_attention as pc
    hq, hkv, maxp, pages, window = SERVED[arch]
    pool = spec((L, pages, hkv, PS, D), jnp.bfloat16)
    bt, vec = spec((32, maxp), jnp.int32), spec((32,), jnp.int32)
    new = spec((32, hkv, c, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, kp, vp, bt, st, sp, kn, vn, lyr: pc.paged_chunk_attention(
            q, kp, vp, bt, st, sp, kn, vn, layer=lyr, scale=D ** -0.5,
            window=window),
        spec((32, hq, c, D), jnp.bfloat16), pool, pool, bt, vec, vec, new,
        new, spec((1,), jnp.int32))
    ops_ = _kernel_ops(compiled)
    assert len(ops_) == 1 and ops_[0].startswith("paged_chunk_attention")


@pytest.mark.parametrize("c", [1, 32])
def test_paged_mla_chunk_compiles_for_v5e_at_served_pool(spec, c):
    """deepseek-v2-lite's served latent pool: 640 pages and the trash page,
    rows of 640 lanes (512 + 64 padded), batch 32, 32 pages a row."""
    from repro.kernels import paged_chunk_attention as pc
    pool = spec((L, 641, PS, MLA_DP), jnp.bfloat16)
    bt, vec = spec((32, 32), jnp.int32), spec((32,), jnp.int32)
    compiled = _compile(
        lambda q, lp, bt, st, sp, ln, lyr: pc.paged_mla_chunk(
            q, lp, bt, st, sp, ln, r=MLA_R, layer=lyr, scale=0.1),
        spec((32, H, c, MLA_R + MLA_RD), jnp.float32), pool, bt, vec, vec,
        spec((32, c, MLA_DP), jnp.bfloat16), spec((1,), jnp.int32))
    ops_ = _kernel_ops(compiled)
    assert len(ops_) == 1 and ops_[0].startswith("paged_mla_chunk")


@pytest.mark.parametrize("width", [1, 32])
def test_moe_grouped_matmul_compiles_for_v5e_at_served_shapes(
        one_chip, monkeypatch, width):
    """One expert layer of deepseek-v2-lite as served: d 2048, experts of
    1408, 16 of 64 held, top 6, a batch of 32 rows at span widths 1 and
    32.  The routed experts run in one Mosaic kernel, and its event keeps
    the name the benchmark's matcher sums."""
    import repro.configs as configs
    from repro.kernels import ops
    from repro.models import moe

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = configs.get("deepseek-v2-lite-16b").replace(experts_held=16)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: moe.init(jax.random.PRNGKey(0), cfg)))
    assert params["experts"]["gate"]["w"].shape == (16, 2048, 1408)
    x = jax.ShapeDtypeStruct((32, width, 2048), jnp.bfloat16,
                             sharding=one_chip)
    valid = jax.ShapeDtypeStruct((32, width), jnp.bool_, sharding=one_chip)
    compiled = _compile(lambda p, x, v: moe.forward(p, cfg, x, v),
                        params, x, valid)
    ops_ = _kernel_ops(compiled)
    assert len(ops_) == 1 and ops_[0].startswith("moe_grouped_matmul")


def test_moe_layer_differentiates_for_v5e(one_chip, monkeypatch):
    """The trainer's path: the gradient of a loss through one expert layer
    (deepseek-v2-lite widths, all 64 experts held, 2 x 256 tokens)
    compiles for v5e.  The forward keeps the Mosaic kernel; its backward
    runs as XLA grouped matmuls."""
    import repro.configs as configs
    from repro.kernels import ops
    from repro.models import moe

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = configs.get("deepseek-v2-lite-16b")
    assert cfg.held_experts == (0, 64)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: moe.init(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((2, 256, 2048), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        y, aux, _ = moe.forward(p, cfg, x)
        return jnp.mean(jnp.square(y.astype(jnp.float32))) + 0.01 * aux

    compiled = _compile(jax.grad(loss, argnums=(0, 1)), params, x)
    ops_ = _kernel_ops(compiled)
    assert len([o for o in ops_ if "moe_grouped_matmul" in o]) == 1
    assert any(o.startswith("ragged-dot") for o in ops_)


@pytest.mark.parametrize("kernel", ["paged_chunk_attention",
                                    "paged_chunk_attention_quant",
                                    "paged_mla_chunk",
                                    "paged_mla_chunk_quant"])
def test_chunk_kernels_carry_their_own_names_for_v5e(spec, kernel):
    """Each chunk kernel is named by its ``pallas_call``, not by the jitted
    function around it: called unwrapped inside a function of another
    name, its trace event still starts with the kernel's name (an MHA
    name holds ``paged_chunk_attention``, a quantized one ``_quant``)."""
    from repro.kernels import paged_chunk_attention as pc
    bt, vec = spec((B, MAXP), jnp.int32), spec((B,), jnp.int32)
    width = MLA_R + MLA_RD
    fn = getattr(pc, kernel).__wrapped__
    if kernel == "paged_chunk_attention":
        pool = spec((L, P, H, PS, D), jnp.bfloat16)
        tok = spec((B, H, C, D), jnp.bfloat16)
        args = (tok, pool, pool, bt, vec, vec, tok, tok)
        kw = dict(scale=0.1)
    elif kernel == "paged_chunk_attention_quant":
        pool, scales = (spec((L, P, H, QPS, D), jnp.int8),
                        spec((L, P, H, QPS), jnp.float32))
        new = spec((B, H, C, D), jnp.float32)
        args = (spec((B, H, C, D), jnp.bfloat16), pool, scales, pool,
                scales, bt, vec, vec, new, new)
        kw = dict(scale=0.1, qmax=127.0)
    elif kernel == "paged_mla_chunk":
        args = (spec((B, H, C, width), jnp.float32),
                spec((L, P, PS, MLA_DP), jnp.bfloat16), bt, vec, vec,
                spec((B, C, MLA_DP), jnp.bfloat16))
        kw = dict(r=MLA_R, scale=0.1)
    else:
        args = (spec((B, H, C, width), jnp.float32),
                spec((L, P, QPS, MLA_DP), jnp.int8),
                spec((L, P, QPS), jnp.float32), bt, vec, vec,
                spec((B, C, MLA_DP), jnp.float32))
        kw = dict(r=MLA_R, scale=0.1, qmax=127.0)

    def served_step(layer, *a):
        return fn(*a, layer=layer, **kw)

    ops_ = _kernel_ops(_compile(served_step, spec((1,), jnp.int32), *args))
    assert len(ops_) == 1 and ops_[0].split(".")[0] == kernel


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
        lambda q, lp, bt, st, sp, ln, lyr: pc.paged_mla_chunk(
            q, lp, bt, st, sp, ln, r=MLA_R, layer=lyr, scale=0.1),
        spec((B, H, C, width), jnp.float32), spec((L,) + pool.shape,
                                                  pool.dtype), bt, vec, vec,
        spec((B, C, MLA_DP), jnp.bfloat16), spec((1,), jnp.int32))
    decode = _compile(
        lambda q, lp, bt, pos, ln: pm.paged_mla_decode(
            q, lp, bt, pos, ln, r=MLA_R, scale=0.1),
        spec((B, H, width), jnp.float32), pool, bt, vec,
        spec((B, MLA_DP), jnp.bfloat16))
    assert _has_kernel(chunk) and _has_kernel(decode)


@pytest.mark.parametrize("layers", [1, L])
@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
def test_quantized_mha_kernels_compile_for_v5e(spec, qdtype, layers):
    """The chunk kernel on stacks of one layer (as ``kernels.ops`` passes
    one layer's pools) and of several, beside the decode kernel."""
    from repro.kernels import paged_chunk_attention as pc
    from repro.kernels import paged_decode_attention as pd
    pool, scales = spec((P, H, QPS, D), qdtype), spec((P, H, QPS), jnp.float32)
    stack, sstack = (spec((layers,) + pool.shape, qdtype),
                     spec((layers,) + scales.shape, jnp.float32))
    bt, vec = spec((B, MAXP), jnp.int32), spec((B,), jnp.int32)
    chunk = _compile(
        lambda q, kp, ks, vp, vs, bt, st, sp, kn, vn, lyr:
        pc.paged_chunk_attention_quant(q, kp, ks, vp, vs, bt, st, sp, kn, vn,
                                       layer=lyr, scale=0.1, qmax=127.0),
        spec((B, H, C, D), jnp.bfloat16), stack, sstack, stack, sstack, bt,
        vec, vec, spec((B, H, C, D), jnp.float32),
        spec((B, H, C, D), jnp.float32), spec((1,), jnp.int32))
    decode = _compile(
        lambda q, kp, ks, vp, vs, bt, pos, kn, vn:
        pd.paged_decode_attention_quant(q, kp, ks, vp, vs, bt, pos, kn, vn,
                                        scale=0.1, qmax=127.0),
        spec((B, H, D), jnp.bfloat16), pool, scales, pool, scales, bt, vec,
        spec((B, H, D), jnp.float32), spec((B, H, D), jnp.float32))
    assert _has_kernel(chunk) and _has_kernel(decode)


@pytest.mark.parametrize("layers", [1, L])
@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
def test_quantized_mla_kernels_compile_for_v5e(spec, qdtype, layers):
    """As the MHA test: the latent chunk kernel on stacks of one layer and
    of several, beside the decode kernel."""
    from repro.kernels import paged_chunk_attention as pc
    from repro.kernels import paged_mla_decode as pm
    pool, scales = spec((P, QPS, MLA_DP), qdtype), spec((P, QPS), jnp.float32)
    stack, sstack = (spec((layers,) + pool.shape, qdtype),
                     spec((layers,) + scales.shape, jnp.float32))
    bt, vec = spec((B, MAXP), jnp.int32), spec((B,), jnp.int32)
    width = MLA_R + MLA_RD
    chunk = _compile(
        lambda q, lp, ls, bt, st, sp, ln, lyr: pc.paged_mla_chunk_quant(
            q, lp, ls, bt, st, sp, ln, r=MLA_R, layer=lyr, scale=0.1,
            qmax=127.0),
        spec((B, H, C, width), jnp.float32), stack, sstack, bt, vec, vec,
        spec((B, C, MLA_DP), jnp.float32), spec((1,), jnp.int32))
    decode = _compile(
        lambda q, lp, ls, bt, pos, ln: pm.paged_mla_decode_quant(
            q, lp, ls, bt, pos, ln, r=MLA_R, scale=0.1, qmax=127.0),
        spec((B, H, width), jnp.float32), pool, scales, bt, vec,
        spec((B, MLA_DP), jnp.float32))
    assert _has_kernel(chunk) and _has_kernel(decode)


def _served_cfg(arch: str = "olmo-1b"):
    """A served cell's model at its published widths, depth cut to two
    scanned layers (deepseek-v2-lite keeps its dense lead layer and holds
    16 of its 64 experts, as its cell does)."""
    import repro.configs as configs
    if arch == "deepseek-v2-lite-16b":
        return dataclasses.replace(configs.get(arch), num_layers=3,
                                   experts_first=0, experts_held=16)
    return dataclasses.replace(configs.get(arch), num_layers=2)


def _served_step_compiled(one_chip, monkeypatch, make, width, *extra,
                          cfg=None, batch=B, max_len=2048, num_pages=None):
    """Compile a served-step constructor, platform-resolved to the kernels,
    at a served model's published widths (``_served_cfg``; olmo-1b unless
    ``cfg`` is given), ``batch`` rows of ``max_len`` over ``num_pages``
    pages a layer (the default: every row's pages and the trash page)."""
    from repro.kernels import ops
    from repro.models import lm

    # The described chip is not the default backend; steer the platform
    # choice the engine makes to the one the chip would make.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.default_impl() == "pallas"
    cfg = cfg or _served_cfg()

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shaped(lm.abstract_params(cfg))
    cache = shaped(jax.eval_shape(lambda: lm.init_cache(
        cfg, batch, max_len, paged=True, page_size=PS,
        num_pages=num_pages)))
    vec = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    step = jax.jit(make(cfg), donate_argnums=(1,))
    return step.lower(
        params, cache, jax.ShapeDtypeStruct((batch, width), jnp.int32,
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


# (batch, max_len, pages a layer, the trash page included) of the served
# cells' engines.
SERVED_POOLS = {"olmo-1b": (32, 2048, 641),
                "starcoder2-15b": (32, 4096, 2049),
                "deepseek-v2-lite-16b": (32, 2048, 641)}


@pytest.mark.parametrize("width", [1, 32])
@pytest.mark.parametrize("arch", sorted(SERVED_POOLS))
def test_mixed_step_moves_no_page_pool_for_v5e(one_chip, monkeypatch, arch,
                                               width):
    """The layer scan hands the kernels the stacked pools and expert banks
    and the layer index: the compiled step of each served cell's model and
    pools (MHA, GQA with a window, latent attention with held experts)
    slices, updates and copies no page pool or expert bank, neither one
    layer's nor the ``[L, ...]`` stack, and its temporaries hold less than
    one layer's smallest pool or bank."""
    from repro.models import cache as cache_mod
    from repro.models import lm
    from repro.serving import engine as engine_mod
    cfg = _served_cfg(arch)
    batch, max_len, pages = SERVED_POOLS[arch]
    compiled = _served_step_compiled(
        one_chip, monkeypatch, engine_mod.make_mixed_step_fn, width,
        jax.ShapeDtypeStruct((2,), jnp.uint32), cfg=cfg, batch=batch,
        max_len=max_len, num_pages=pages)
    groups = jax.eval_shape(lambda: lm.init_cache(
        cfg, batch, max_len, paged=True, page_size=PS,
        num_pages=pages))["groups"]
    stacks = [gc[name] for gc in groups.values()
              for name in cache_mod.pool_leaves(gc, cache_mod.layout_of(gc))]
    stacks += [w for gp in lm.abstract_params(cfg)["groups"].values()
               if "experts" in gp.get("ffn", {})
               for w in jax.tree.leaves(gp["ffn"]["experts"])]
    assert len(stacks) == (4 if arch == "deepseek-v2-lite-16b" else 2)
    shapes = {",".join(map(str, sh)) for st in stacks
              for sh in (st.shape, st.shape[1:])}
    moved = [line for line in compiled.as_text().splitlines()
             if (m := re.match(r"\s*(?:ROOT )?%([\w.-]+) = \(?\w+\[([\d,]*)\]",
                               line))
             and re.search(r"dynamic-slice|dynamic-update-slice|copy",
                           m.group(1))
             and m.group(2) in shapes]
    assert moved == []
    layer_bytes = min(st.size // st.shape[0] * st.dtype.itemsize
                      for st in stacks)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


def test_verify_step_compiles_for_v5e_at_olmo_widths(one_chip, monkeypatch):
    """The speculative verify step: all-logits spans of 1 + 4 drafts."""
    from repro.serving import engine as engine_mod
    compiled = _served_step_compiled(
        one_chip, monkeypatch, engine_mod.make_verify_step_fn, 5)
    assert _has_kernel(compiled)
