"""Partitioner unit tests: spec rules, divisibility guards, FSDP, caches."""
from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.models import lm
from repro.sharding.partition import Partitioner

# Specs are pure metadata — a tiny mesh with the production axis names is
# enough to unit-test the rules (sizes chosen to exercise divisibility).
pytestmark = pytest.mark.usefixtures()


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


class FakeMesh:
    """Mesh stand-in with arbitrary axis sizes (specs never touch devices)."""
    def __init__(self, data=16, model=16):
        self.shape = {"data": data, "model": model}
        self.axis_names = ("data", "model")


def test_attention_param_rules():
    part = Partitioner(FakeMesh(), fsdp=False)
    assert part.param_spec(("blk", "attn", "wq", "w"), (1024, 2048)) == \
        P(None, "model")
    assert part.param_spec(("blk", "attn", "wo", "w"), (2048, 1024)) == \
        P("model", None)
    # Stacked (scan) params get a leading None.
    assert part.param_spec(("groups", "0", "attn", "wq", "w"),
                           (8, 1024, 2048)) == P(None, None, "model")


def test_divisibility_guard_replicates():
    part = Partitioner(FakeMesh(model=16), fsdp=False)
    # MQA kv projection with 1 head * 128 dims = 128 columns: divisible.
    assert part.param_spec(("a", "wk", "w"), (1024, 128)) == P(None, "model")
    # Odd vocab (whisper): embed rows not divisible -> replicated.
    assert part.param_spec(("embed", "w"), (51865, 384)) == P(None, None)


def test_fsdp_adds_data_axis():
    part = Partitioner(FakeMesh(data=16, model=16), fsdp=True)
    spec = part.param_spec(("a", "ffn", "up", "w"), (4096, 16384))
    assert spec == P(("data",), "model")
    # 1-D params are never FSDP-sharded.
    assert part.param_spec(("norm", "scale"), (4096,)) == P(None)


def test_moe_expert_parallel():
    part = Partitioner(FakeMesh(), fsdp=False)
    assert part.param_spec(("ffn", "experts", "gate"), (64, 2048, 1408)) == \
        P("model", None, None)


def test_cache_specs_head_vs_seq():
    part = Partitioner(FakeMesh(model=16), fsdp=False)
    # 16 kv heads: shard heads.
    s = part.cache_entry_spec(("groups", "0", "k"), (8, 128, 16, 32768, 128),
                              shard_batch=True, stacked=True)
    assert s == P(None, ("data",), "model", None, None)
    # 8 kv heads (not divisible): shard sequence instead.
    s = part.cache_entry_spec(("k",), (128, 8, 32768, 128),
                              shard_batch=True, stacked=False)
    assert s == P(("data",), None, "model", None)


def test_mla_cache_replication_variant():
    base = Partitioner(FakeMesh(), fsdp=False)
    repl = Partitioner(FakeMesh(), fsdp=False, mla_cache="replicated")
    shape = (128, 32768, 512)
    assert base.cache_entry_spec(("ckv",), shape, shard_batch=True,
                                 stacked=False) == P(("data",), None, "model")
    assert repl.cache_entry_spec(("ckv",), shape, shard_batch=True,
                                 stacked=False) == P(("data",), None, None)
    seq = Partitioner(FakeMesh(), fsdp=False, mla_cache="seq")
    assert seq.cache_entry_spec(("ckv",), shape, shard_batch=True,
                                stacked=False) == P(("data",), "model", None)


def test_every_arch_param_tree_gets_specs():
    """No param path falls through the rules with a wrong-rank spec."""
    part = Partitioner(FakeMesh(), fsdp=True)
    for name in configs.ARCHS:
        cfg = configs.reduced(configs.get(name))
        abs_p = lm.abstract_params(cfg)
        specs = part.params_specs(abs_p)
        for leaf, spec in zip(jax.tree.leaves(abs_p), jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P))):
            assert len(spec) <= len(leaf.shape)


def test_paged_pool_leaf_rules():
    """Pools shard over heads (MHA) / latent features (MLA); the page dim is
    shared across rows (never batch-sharded); block tables replicate."""
    part = Partitioner(FakeMesh(model=16), fsdp=False)
    # MHA pool [P, Hkv, ps, D], 16 heads: heads on model, page dim whole.
    s = part.cache_entry_spec(("groups", "0", "k_pages"),
                              (8, 4096, 16, 64, 128),
                              shard_batch=True, stacked=True)
    assert s == P(None, None, "model", None, None)
    # Non-divisible heads replicate (no fallback onto the page dim).
    s = part.cache_entry_spec(("v_pages",), (4096, 6, 64, 128),
                              shard_batch=True, stacked=False)
    assert s == P(None, None, None, None)
    # MLA latent pool [P, ps, Dp]: latent-feature axis on model.
    s = part.cache_entry_spec(("latent_pages",), (4096, 64, 640),
                              shard_batch=True, stacked=False)
    assert s == P(None, None, "model")
    # Block tables replicate everywhere.
    s = part.cache_entry_spec(("groups", "0", "block_tables"), (8, 128, 64),
                              shard_batch=True, stacked=True)
    assert s == P(None, None, None)


def test_paged_cache_tree_gets_specs():
    """The full paged cache tree (MHA and MLA archs) maps through the
    partitioner with correct ranks."""
    part = Partitioner(FakeMesh(), fsdp=False)
    for arch in ("olmo-1b", "deepseek-v2-lite-16b"):
        cfg = configs.reduced(configs.get(arch))
        cache = jax.eval_shape(
            lambda c=cfg: lm.init_cache(c, 8, 256, paged=True, page_size=64))
        specs = jax.tree_util.tree_map_with_path(
            lambda path, leaf: part.cache_entry_spec(
                tuple(getattr(k, "key", getattr(k, "name", k))
                      for k in path),
                np.shape(leaf), shard_batch=True,
                stacked="groups" in str(path)),
            cache)
        for leaf, spec in zip(jax.tree.leaves(cache),
                              jax.tree.leaves(
                                  specs,
                                  is_leaf=lambda x: isinstance(x, P))):
            assert len(spec) <= len(leaf.shape)


def test_paged_decode_lowers_multi_device():
    """The fused paged step (MHA pools + MLA latent pools) lowers and
    compiles on a multi-device mesh with the pool sharding rules — spawned
    with 8 host devices so the main process keeps its single-device view."""
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    src = str(Path(__file__).resolve().parent.parent / "src")
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.configs as configs
        from repro.models import lm
        from repro.serving import engine as engine_mod
        from repro.sharding.partition import Partitioner
        from repro.sharding import activation

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for arch in ("olmo-1b", "deepseek-v2-lite-16b"):
            cfg = configs.reduced(configs.get(arch))
            part = Partitioner(mesh, fsdp=False)
            p_abs = lm.abstract_params(cfg)
            p_shard = part.params_shardings(p_abs)
            b = 8
            cache_abs = jax.eval_shape(
                lambda c=cfg: lm.init_cache(c, b, 256, paged=True,
                                            page_size=64))
            c_shard = part.cache_shardings(cache_abs, shard_batch=True)
            bspec = NamedSharding(mesh, P(("data",)))
            sd = jax.ShapeDtypeStruct
            binding = activation.standard_binding(("data",),
                                                  seq_parallel=True)
            with activation.bind(binding):
                jitted = jax.jit(
                    engine_mod.make_serve_step(cfg),
                    in_shardings=(p_shard, c_shard, bspec, bspec,
                                  NamedSharding(mesh, P(None))),
                    donate_argnums=(1,))
                with mesh:
                    jitted.lower(p_abs, cache_abs, sd((b,), jnp.int32),
                                 sd((b,), jnp.int32),
                                 sd((2,), jnp.uint32)).compile()
            print(arch, "OK")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin",
                              "HOME": "/root"},
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "olmo-1b OK" in out.stdout
    assert "deepseek-v2-lite-16b OK" in out.stdout
