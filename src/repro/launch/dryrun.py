"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers, compiles,
fits, and records its roofline terms — without hardware.

For each cell this script:
  1. builds abstract params/optimizer/cache/batch (jax.eval_shape — nothing
     is allocated),
  2. ``jax.jit(step, in_shardings=...).lower(...).compile()`` on the
     production mesh (16×16 single-pod, 2×16×16 multi-pod),
  3. records ``compiled.memory_analysis()`` (fits?), ``cost_analysis()``
     (FLOPs/bytes), and collective bytes parsed from the optimized HLO,
  4. writes one JSON per cell under experiments/dryrun/ (incremental:
     existing cells are skipped unless --force).

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --arch deepseek-moe-16b --shape train_4k \
      --mesh single --variant no_remat --no-remat
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import: jax locks the device count on first init.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.configs.shapes import SHAPES, ShapeSpec, applicable
from repro.launch import mesh as mesh_mod
from repro.models import lm
from repro.serving import engine as engine_mod
from repro.sharding.partition import Partitioner
from repro.training import optimizer as opt_mod
from repro.training.train_step import make_train_step

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Bytes through each device's links, per collective op (ring model).

    Refines the brief's "sum operand sizes": operand-only counting
    undercounts all-gather by the group size (each device streams the full
    output through its links in a ring) and all-reduce by 2× (reduce-scatter
    + all-gather phases).  Counted per op:
        all-gather           output bytes
        all-reduce           2 × operand bytes
        reduce-scatter       operand bytes
        all-to-all           operand bytes
        collective-permute   operand bytes
    """
    totals: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "= " not in line:
            continue
        op = m.group(1)
        operand_part = line[m.end():]
        out_part = line[: m.start()]
        operands = _SHAPE_RE.findall(operand_part)
        outputs = _SHAPE_RE.findall(out_part)
        op_bytes = sum(_shape_bytes(dt, dims) for dt, dims in operands)
        out_bytes = sum(_shape_bytes(dt, dims) for dt, dims in outputs)
        if op == "all-gather":
            b = out_bytes or op_bytes
        elif op == "all-reduce":
            b = 2 * (op_bytes or out_bytes)
        else:
            b = op_bytes or out_bytes
        totals[op] = totals.get(op, 0.0) + b
        totals["total"] = totals.get("total", 0.0) + b
    return totals


# ---------------------------------------------------------------------------
# Abstract inputs per (arch, shape)
# ---------------------------------------------------------------------------

def batch_specs(cfg, shape: ShapeSpec):
    """ShapeDtypeStructs for a training batch."""
    b, t = shape.global_batch, shape.seq_len
    sd = jax.ShapeDtypeStruct
    batch = {
        "tokens": sd((b, t), jnp.int32),
        "targets": sd((b, t), jnp.int32),
        "loss_mask": sd((b, t), jnp.float32),
    }
    if cfg.num_prefix_tokens:
        batch["prefix_embeds"] = sd((b, cfg.num_prefix_tokens, cfg.d_model),
                                    jnp.bfloat16)
    if cfg.is_encdec:
        batch["enc_frames"] = sd((b, cfg.encoder.seq_len, cfg.d_model),
                                 jnp.bfloat16)
    return batch


def input_specs(arch: str, shape_name: str):
    """Public helper: abstract inputs for one cell (no allocation)."""
    cfg = configs.get(arch)
    return batch_specs(cfg, SHAPES[shape_name])


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree,
        is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))


def _sharding_tree(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Cell runners
# ---------------------------------------------------------------------------

def lower_cell(cfg, shape: ShapeSpec, mesh, *, merge_strategy="pmax",
               fused_coord=False, microbatches=1, remat=True,
               seq_parallel=True, mla_cache="latent",
               merge_every=1, delta_capacity=64, kv_layout="dense",
               page_size=64):
    """Returns the lowered computation. Never allocates device memory.

    Training cells use FSDP (fully-sharded params/grads/optimizer — the
    at-scale default); serving cells keep params tensor-parallel only
    (per-token FSDP all-gathers would destroy decode latency).
    """
    from repro.sharding import activation
    dp_for_bind = mesh_mod.dp_axes(mesh)
    binding = activation.standard_binding(dp_for_bind,
                                          seq_parallel=seq_parallel)
    with activation.bind(binding):
        return _lower_cell_inner(cfg, shape, mesh,
                                 merge_strategy=merge_strategy,
                                 fused_coord=fused_coord,
                                 microbatches=microbatches, remat=remat,
                                 mla_cache=mla_cache,
                                 merge_every=merge_every,
                                 delta_capacity=delta_capacity,
                                 kv_layout=kv_layout, page_size=page_size)


def _lower_cell_inner(cfg, shape: ShapeSpec, mesh, *, merge_strategy="pmax",
                      fused_coord=False, microbatches=1, remat=True,
                      mla_cache="latent", merge_every=1, delta_capacity=64,
                      kv_layout="dense", page_size=64):
    part = Partitioner(mesh, fsdp=(shape.kind == "train"),
                       mla_cache=mla_cache)
    p_abs = lm.abstract_params(cfg)
    p_shard = part.params_shardings(p_abs)
    dp = mesh_mod.dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    if shape.kind == "train":
        opt = opt_mod.AdamW()
        o_abs = jax.eval_shape(opt.init, p_abs)
        # FSDP already spreads params over data axes; Adam moments follow the
        # param sharding exactly (this IS ZeRO: opt state fully sharded).
        p_specs = part.params_specs(p_abs)
        o_specs = opt_mod.AdamWState(step=P(), mu=p_specs, nu=p_specs)
        o_shard = _sharding_tree(mesh, o_specs)
        batch = batch_specs(cfg, shape)
        b_shard = jax.tree.map(
            lambda x: NamedSharding(
                mesh, P(dp if x.shape[0] % dp_size == 0 else None,
                        *([None] * (len(x.shape) - 1)))), batch)
        step_fn = make_train_step(cfg, opt, remat=remat,
                                  microbatches=microbatches)
        jitted = jax.jit(step_fn, in_shardings=(p_shard, o_shard, b_shard),
                         donate_argnums=(0, 1))
        with mesh:
            lowered = jitted.lower(p_abs, o_abs, batch)
        return lowered

    b = shape.global_batch
    shard_batch = b % dp_size == 0
    # VLM prefix tokens occupy cache positions too.
    max_len = shape.seq_len + cfg.num_prefix_tokens
    # kv_layout="paged" lowers the fused paged step: pool leaves shard over
    # heads (MHA) / the latent-feature axis (MLA), block tables replicate
    # (see sharding/partition.py) — the multi-host proof for the paged path.
    cache_abs = jax.eval_shape(
        lambda: lm.init_cache(cfg, b, max_len,
                              paged=(kv_layout == "paged"),
                              page_size=page_size))
    c_shard = part.cache_shardings(cache_abs, shard_batch=shard_batch)
    bspec = NamedSharding(mesh, P(dp if shard_batch else None))

    if shape.kind == "prefill":
        sd = jax.ShapeDtypeStruct
        tokens = sd((b, shape.seq_len), jnp.int32)
        tok_shard = NamedSharding(
            mesh, P(dp if shard_batch else None, None))
        # Stub frontends enter as positional args (pjit rejects kwargs when
        # in_shardings is given).
        stub_args = []
        stub_shards = []
        stub_sharding = NamedSharding(
            mesh, P(dp if shard_batch else None, None, None))
        if cfg.num_prefix_tokens:
            stub_args.append(sd((b, cfg.num_prefix_tokens, cfg.d_model),
                                jnp.bfloat16))
            stub_shards.append(stub_sharding)
        if cfg.is_encdec:
            stub_args.append(sd((b, cfg.encoder.seq_len, cfg.d_model),
                                jnp.bfloat16))
            stub_shards.append(stub_sharding)
        prefill_fn = engine_mod.make_prefill_fn(cfg)

        if cfg.num_prefix_tokens:
            def fn(params, cache, tokens, prefix_embeds):
                return prefill_fn(params, cache, tokens,
                                  prefix_embeds=prefix_embeds)
        elif cfg.is_encdec:
            def fn(params, cache, tokens, enc_frames):
                return prefill_fn(params, cache, tokens,
                                  enc_frames=enc_frames)
        else:
            def fn(params, cache, tokens):
                return prefill_fn(params, cache, tokens)

        jitted = jax.jit(
            fn, in_shardings=(p_shard, c_shard, tok_shard, *stub_shards),
            donate_argnums=(1,))
        with mesh:
            lowered = jitted.lower(p_abs, cache_abs, tokens, *stub_args)
        return lowered

    # decode
    sd = jax.ShapeDtypeStruct
    token = sd((b,), jnp.int32)
    pos = sd((b,), jnp.int32)
    if fused_coord:
        n_rep = dp_size
        from repro.core import doc as doc_mod, gset

        def coord_template():
            base = {"doc": doc_mod.empty(64, 2048),
                    "heartbeats": gset.GCounter.zeros(n_rep)}
            if merge_strategy == "delta":
                base = engine_mod.with_delta_frontier(base)
            return engine_mod.replicate_coord(base, n_rep)

        coord_abs = jax.eval_shape(coord_template)
        coord_shard = jax.tree.map(
            lambda x: NamedSharding(mesh, P(dp, *([None] * (x.ndim - 1)))),
            coord_abs)
        step_fn = engine_mod.make_fused_serve_step(
            cfg, mesh, dp, merge_strategy=merge_strategy,
            merge_every=merge_every, delta_capacity=delta_capacity)
        slots = sd((b,), jnp.int32)
        active = sd((b,), jnp.bool_)
        stepi = sd((), jnp.int32)
        jitted = jax.jit(
            step_fn,
            in_shardings=(p_shard, c_shard, bspec, bspec, bspec, bspec,
                          coord_shard, NamedSharding(mesh, P())),
            donate_argnums=(1,))
        with mesh:
            lowered = jitted.lower(p_abs, cache_abs, token, pos, slots,
                                   active, coord_abs, stepi)
        return lowered

    serve_fn = engine_mod.make_serve_step(cfg)
    rng = sd((2,), jnp.uint32)
    jitted = jax.jit(
        serve_fn,
        in_shardings=(p_shard, c_shard, bspec, bspec,
                      NamedSharding(mesh, P(None))),
        donate_argnums=(1,))
    with mesh:
        lowered = jitted.lower(p_abs, cache_abs, token, pos, rng)
    return lowered


def analytic_memory(cfg, shape: ShapeSpec, mesh) -> dict[str, int]:
    """Exact per-device bytes of persistent state from the real shardings.

    The CPU backend's memory_analysis over-reports temp (it materializes f32
    copies of every bf16 weight for matmuls — no native bf16 FMA on host;
    TPU MXUs consume bf16 directly), so the fits-in-HBM judgement uses these
    analytic numbers plus the HLO-inspected transient (EXPERIMENTS.md).
    """
    part = Partitioner(mesh, fsdp=(shape.kind == "train"))
    p_abs = lm.abstract_params(cfg)
    p_shard = part.params_shardings(p_abs)

    def shard_bytes(abs_tree, shardings):
        total = 0
        for leaf, sh in zip(jax.tree.leaves(abs_tree),
                            jax.tree.leaves(shardings)):
            local = sh.shard_shape(leaf.shape)
            n = 1
            for d in local:
                n *= d
            total += n * jnp.dtype(leaf.dtype).itemsize
        return int(total)

    out = {"params_per_device": shard_bytes(p_abs, p_shard)}
    if shape.kind == "train":
        opt = opt_mod.AdamW()
        o_abs = jax.eval_shape(opt.init, p_abs)
        p_specs = part.params_specs(p_abs)
        o_shard = _sharding_tree(
            mesh, opt_mod.AdamWState(step=P(), mu=p_specs, nu=p_specs))
        out["opt_per_device"] = shard_bytes(o_abs, o_shard)
    else:
        dp = mesh_mod.dp_axes(mesh)
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        cache_abs = jax.eval_shape(
            lambda: lm.init_cache(cfg, shape.global_batch,
                                  shape.seq_len + cfg.num_prefix_tokens))
        c_shard = part.cache_shardings(
            cache_abs, shard_batch=shape.global_batch % dp_size == 0)
        out["cache_per_device"] = shard_bytes(cache_abs, c_shard)
    out["total_per_device"] = sum(out.values())
    return out


def _costs_of(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def extrapolated_costs(cfg, shape, mesh, **kw) -> dict:
    """True per-cell costs via two-point extrapolation over layer groups.

    XLA's HloCostAnalysis visits while-loop bodies ONCE (verified), so the
    full model's scan-over-groups undercounts FLOPs/bytes/collective bytes by
    ~G×.  Lowering 1-group and 2-group variants gives the exact marginal cost
    of one group (identical HLO body); total = f(1) + (G-1)·(f(2)-f(1)).
    Inner time-recurrence scans (xLSTM cells, RG-LRU) keep their heavy
    matmuls outside the loop, so their residual undercount is <1% (noted in
    EXPERIMENTS.md).
    """
    g = cfg.pattern_groups
    pat, tail = len(cfg.block_pattern), len(cfg.tail_blocks)

    def variant(groups):
        kw_c = {"num_layers": cfg.first_dense + groups * pat + tail}
        if cfg.encoder is not None:
            kw_c["encoder"] = cfg.encoder.__class__(
                num_layers=groups, num_heads=cfg.encoder.num_heads,
                seq_len=cfg.encoder.seq_len)
        return cfg.replace(**kw_c)

    with lm.unrolled_scans():
        c1 = _costs_of(lower_cell(variant(1), shape, mesh, **kw).compile())
        if g < 2:
            return {"flops": c1["flops"], "bytes": c1["bytes"],
                    "coll_total": c1["coll"].get("total", 0.0),
                    "coll": c1["coll"], "method": "direct-unrolled"}
        c2 = _costs_of(lower_cell(variant(2), shape, mesh, **kw).compile())
    est = {
        "flops": c1["flops"] + (g - 1) * (c2["flops"] - c1["flops"]),
        "bytes": c1["bytes"] + (g - 1) * (c2["bytes"] - c1["bytes"]),
        "method": "two-point group extrapolation",
    }
    coll = {}
    for k in set(c1["coll"]) | set(c2["coll"]):
        a, b = c1["coll"].get(k, 0.0), c2["coll"].get(k, 0.0)
        coll[k] = a + (g - 1) * (b - a)
    est["coll"] = coll
    est["coll_total"] = coll.get("total", 0.0)
    return est


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             variant: str = "baseline", force: bool = False,
             merge_strategy: str = "pmax", fused_coord: bool = False,
             remat: bool = True, microbatches: int = 1,
             mla_cache: str = "latent", merge_every: int = 1,
             delta_capacity: int = 64, ring_cache: bool = False,
             kv_layout: str = "dense", page_size: int = 64) -> dict:
    shape = SHAPES[shape_name]
    cfg = configs.get(arch)
    if ring_cache:
        cfg = cfg.replace(ring_local_cache=True)

    out_dir = OUT_DIR / mesh_kind
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{arch}__{shape_name}__{variant}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "variant": variant, "kind": shape.kind}
    ok, reason = applicable(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        out_path.write_text(json.dumps(record, indent=2))
        return record

    mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape, mesh, merge_strategy=merge_strategy,
                             fused_coord=fused_coord, remat=remat,
                             microbatches=microbatches,
                             mla_cache=mla_cache,
                             merge_every=merge_every,
                             delta_capacity=delta_capacity,
                             kv_layout=kv_layout, page_size=page_size)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        raw = _costs_of(compiled)
        # Cost lowers use microbatches=1: FLOPs/bytes are microbatch-
        # invariant and the mb-scan would hide costs from HloCostAnalysis.
        est = extrapolated_costs(
            cfg, shape, mesh, merge_strategy=merge_strategy,
            fused_coord=fused_coord, remat=remat, microbatches=1,
            mla_cache=mla_cache,
            merge_every=merge_every, delta_capacity=delta_capacity,
            kv_layout=kv_layout, page_size=page_size)
        record.update(
            status="ok", n_devices=int(n_dev),
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={k: int(getattr(mem, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes",
                              "generated_code_size_in_bytes")
                    if hasattr(mem, k)},
            # Raw full-compile numbers (scan bodies counted once — see
            # extrapolated_costs docstring) kept for reference:
            raw_flops_per_device=raw["flops"],
            raw_bytes_per_device=raw["bytes"],
            # Extrapolated per-device costs (the roofline inputs):
            flops_per_device=est["flops"],
            bytes_per_device=est["bytes"],
            collective_bytes_per_device=est["coll"],
            cost_method=est["method"],
            model_flops_est=_model_flops(cfg, shape),
            memory_analytic=analytic_memory(cfg, shape, mesh),
        )
    except Exception as e:  # record the failure; the suite reports it
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    out_path.write_text(json.dumps(record, indent=2))
    return record


def _model_flops(cfg, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N_active·D tokens (train) / 2·N_active·D (inference)."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    factor = 6 if shape.kind == "train" else 2
    return float(factor * n * tokens)


# Per-arch microbatch counts for train_4k: chosen so the full compile fits
# 16 GB/chip (per-device batch 16 is split into this many accumulation
# steps; see EXPERIMENTS.md §Dry-run).
TRAIN_MICROBATCHES = {
    "command-r-plus-104b": 8,
    "granite-34b": 4,
    "starcoder2-15b": 2,
    "paligemma-3b": 2,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--merge-strategy", default="pmax",
                    choices=["pmax", "allgather", "delta"])
    ap.add_argument("--fused-coord", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mla-cache", default="latent",
                    choices=["latent", "replicated", "seq"])
    ap.add_argument("--merge-every", type=int, default=1)
    ap.add_argument("--delta-capacity", type=int, default=64)
    ap.add_argument("--ring-cache", action="store_true")
    ap.add_argument("--kv", default="dense", choices=["dense", "paged"],
                    help="KV cache layout for serving cells")
    ap.add_argument("--page-size", type=int, default=64)
    args = ap.parse_args()

    archs = sorted(configs.ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                t0 = time.time()
                mb = args.microbatches
                if mb == 1 and shape_name == "train_4k":
                    mb = TRAIN_MICROBATCHES.get(arch, 1)
                rec = run_cell(
                    arch, shape_name, mesh_kind, variant=args.variant,
                    force=args.force, merge_strategy=args.merge_strategy,
                    fused_coord=args.fused_coord,
                    remat=not args.no_remat,
                    microbatches=mb,
                    mla_cache=args.mla_cache,
                    merge_every=args.merge_every,
                    delta_capacity=args.delta_capacity,
                    ring_cache=args.ring_cache,
                    kv_layout=args.kv, page_size=args.page_size)
                status = rec.get("status")
                extra = (rec.get("reason") or rec.get("error", "")
                         )[:80] if status != "ok" else (
                    f"flops/dev={rec['flops_per_device']:.3e} "
                    f"coll={rec['collective_bytes_per_device'].get('total', 0):.3e}B")
                print(f"[{mesh_kind}] {arch} × {shape_name} ({args.variant}): "
                      f"{status} ({time.time()-t0:.1f}s) {extra}", flush=True)


if __name__ == "__main__":
    main()
