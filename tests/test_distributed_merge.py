"""Collective CRDT merges on a real multi-device mesh.

Spawned as a subprocess with 8 host devices (the main pytest process must
keep the single-device view for everything else).  Verifies that the
all-gather and pmax merge strategies both produce the exact join across
divergent per-device replicas — the "collectives are the relay" claim —
and that the fused serve step lowers on the debug mesh.
"""
from __future__ import annotations

import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import doc as doc_mod, gset, lww, merge, todo
    from repro.serving import engine as engine_mod

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    R = 4

    # --- divergent per-replica LWW boards: each data shard wrote its own key
    K = 8
    def make_replica(i):
        b = todo.empty(K)
        b = todo.post(b, i, jnp.zeros((K,), bool), jnp.int32(10 + i),
                      jnp.int32(i + 1))
        b = todo.claim(b, i, jnp.int32(i + 1), jnp.int32(20 + i), jnp.int32(0))
        return b
    replicas = [make_replica(i) for i in range(R)]
    expected = merge.fold_join(replicas)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *replicas)

    for strategy in ("pmax", "allgather"):
        def local(st):
            s = jax.tree.map(lambda x: jnp.squeeze(x, 0), st)
            m = merge.collective_merge(s, "data", strategy)
            return jax.tree.map(lambda x: x[None], m)
        specs = jax.tree.map(lambda x: P("data", *([None] * (x.ndim - 1))),
                             stacked)
        out = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                                    out_specs=specs,
                                    check_vma=False))(stacked)
        for i in range(R):
            got = jax.tree.map(lambda x: np.asarray(x[i]), out)
            want = jax.tree.map(np.asarray, expected)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_array_equal(g, w)
        print(f"{strategy}: exact join on all replicas OK")

    # --- delta-state ring sync: O(Δ) ppermute exchange on the real mesh -----
    from repro.core import delta as delta_mod
    R_docs = []
    base_doc = doc_mod.empty(4, 16)
    for i in range(R):
        R_docs.append(doc_mod.append(base_doc, i,
                                     jnp.asarray([i + 1, i + 2, 0, 0]), 2))
    expected_delta = merge.fold_join(R_docs)
    stacked_docs = jax.tree.map(lambda *xs: jnp.stack(xs), *R_docs)
    fr0 = delta_mod.frontier(base_doc)
    fr_stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape),
                              fr0)

    def local_delta(st, fr):
        s = jax.tree.map(lambda x: jnp.squeeze(x, 0), st)
        f = jax.tree.map(lambda x: jnp.squeeze(x, 0), fr)
        m, f2 = merge.delta_merge(s, f, ("data",), (R,), capacity=8)
        return (jax.tree.map(lambda x: x[None], m),
                jax.tree.map(lambda x: x[None], f2))

    d_specs = jax.tree.map(lambda x: P("data", *([None] * (x.ndim - 1))),
                           stacked_docs)
    f_specs = jax.tree.map(lambda x: P("data", *([None] * (x.ndim - 1))),
                           fr_stacked)
    out_docs, out_fr = jax.jit(jax.shard_map(
        local_delta, mesh=mesh, in_specs=(d_specs, f_specs),
        out_specs=(d_specs, f_specs), check_vma=False))(stacked_docs,
                                                        fr_stacked)
    want_fr = delta_mod.frontier(expected_delta)
    for i in range(R):
        got = jax.tree.map(lambda x: np.asarray(x[i]), out_docs)
        for g, w in zip(jax.tree.leaves(got),
                        jax.tree.leaves(jax.tree.map(np.asarray,
                                                     expected_delta))):
            np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(np.asarray(out_fr.length[i]),
                                      np.asarray(want_fr.length))
    print("delta: exact join on all replicas OK")

    # --- SlotDoc + heartbeat merge through the fused-serve-step helper
    docs = []
    for i in range(R):
        d = doc_mod.empty(4, 16)
        d = doc_mod.append(d, i, jnp.asarray([i + 1, i + 2, 0, 0]), 2)
        docs.append({"doc": d, "heartbeats": gset.GCounter(
            jnp.zeros((R,), jnp.int32).at[i].set(5))})
    expected_doc = merge.fold_join([x["doc"] for x in docs])
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *docs)
    merge_fn = engine_mod.make_coord_merge(mesh, ("data",), "pmax")
    out = jax.jit(merge_fn)(stacked)
    for i in range(R):
        got = jax.tree.map(lambda x: np.asarray(x[i]), out["doc"])
        for g, w in zip(jax.tree.leaves(got),
                        jax.tree.leaves(jax.tree.map(np.asarray, expected_doc))):
            np.testing.assert_array_equal(g, w)
    hb = np.asarray(out["heartbeats"].counts[0])
    np.testing.assert_array_equal(hb, np.full((R,), 5))
    print("fused coord merge OK")

    # --- the fused decode+coordination step EXECUTES on the mesh -----------
    import repro.configs as configs
    from repro.models import lm as lm_mod
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=64)
    params = lm_mod.init(jax.random.PRNGKey(0), cfg)
    B = 8                                   # 2 agent rows per data shard
    cache = lm_mod.init_cache(cfg, B, 16)
    coord = {"doc": doc_mod.empty(8, 16),
             "heartbeats": gset.GCounter.zeros(R)}
    coord = engine_mod.replicate_coord(coord, R)
    step = engine_mod.make_fused_serve_step(cfg, mesh, ("data",))
    token = jnp.arange(2, 2 + B, dtype=jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32) % 8
    active = jnp.ones((B,), bool)
    with mesh:
        for t in range(3):
            token, cache, pos, coord = step(params, cache, token, pos,
                                            slots, active, coord,
                                            jnp.int32(t))
    lengths = np.asarray(coord["doc"].length)
    # All replicas observed all agents' appends (3 tokens per slot).
    for i in range(R):
        np.testing.assert_array_equal(lengths[i], np.full((8,), 3))
    digests = [int(doc_mod.digest(jax.tree.map(lambda x: x[i],
                                               coord["doc"])))
               for i in range(R)]
    assert len(set(digests)) == 1, digests
    print("fused serve step convergence OK")

    # --- the fused step with DELTA coordination also converges -------------
    coord2 = {"doc": doc_mod.empty(8, 16),
              "heartbeats": gset.GCounter.zeros(R)}
    coord2 = engine_mod.replicate_coord(
        engine_mod.with_delta_frontier(coord2), R)
    cache2 = lm_mod.init_cache(cfg, B, 16)
    step2 = engine_mod.make_fused_serve_step(cfg, mesh, ("data",),
                                             merge_strategy="delta",
                                             delta_capacity=8)
    token2 = jnp.arange(2, 2 + B, dtype=jnp.int32)
    pos2 = jnp.zeros((B,), jnp.int32)
    with mesh:
        for t in range(3):
            token2, cache2, pos2, coord2 = step2(params, cache2, token2,
                                                 pos2, slots, active,
                                                 coord2, jnp.int32(t))
    lengths2 = np.asarray(coord2["doc"].length)
    for i in range(R):
        np.testing.assert_array_equal(lengths2[i], np.full((8,), 3))
    digests2 = [int(doc_mod.digest(jax.tree.map(lambda x: x[i],
                                                coord2["doc"])))
                for i in range(R)]
    assert len(set(digests2)) == 1, digests2
    # Frontier tracked every appended token (merge_every=1, no overflow).
    np.testing.assert_array_equal(
        np.asarray(coord2["frontier"]["doc"].length[0]), np.full((8,), 3))
    print("fused delta serve step convergence OK")
    print("ALL_OK")
""")


def test_collective_merges_on_8_devices():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ALL_OK" in proc.stdout
    assert "pmax: exact join" in proc.stdout
    assert "allgather: exact join" in proc.stdout
    assert "delta: exact join" in proc.stdout
    assert "fused delta serve step convergence OK" in proc.stdout
