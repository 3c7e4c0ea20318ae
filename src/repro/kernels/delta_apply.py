"""Pallas TPU kernel: scatter-apply an LWW delta buffer into a register bank.

Delta-state sync (core/delta.py) ships changed registers as a compact buffer
of (idx, key, payload-row) lanes.  Applying it is a scatter guarded by the
LWW win test — irregular memory traffic that XLA lowers to a serial scatter
loop over HBM.  This kernel instead streams the bank once, tile-by-tile in
VMEM, and for each tile sweeps the (small, VMEM-resident) delta buffer:
lane j hits a tile row when ``idx[j]`` falls inside it AND its key beats the
current register key.  Bank tiles are read and written once; the delta
buffer is broadcast-compared on the VPU — bandwidth-bound in the bank, like
kernels/lww_merge.py on which it is modeled.

Sweeping lanes in order gives sequential-max semantics, so duplicate target
indices resolve to the largest key (core/delta.py extraction emits unique
indices; duplicates would hit XLA's unspecified scatter order on the jnp
path).  Empty lanes carry ``idx = -1`` and can never match a row.

``idx``/``key`` live in SMEM (scalar loop reads); payload rows load via a
dynamic sublane slice.  Blocks are 128-aligned (the ops.py wrapper pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _apply_kernel(idx_ref, dkey_ref, dpay_ref, key_ref, pay_ref,
                  key_o_ref, pay_o_ref, *, block_k: int):
    # 2-D iota (Mosaic rejects rank-1 iota on TPU), flattened to the
    # rank-1 row-id vector the block layout uses.
    rows = (pl.program_id(0) * block_k
            + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)[:, 0])
    n_lanes = dkey_ref.shape[0]

    def lane(j, carry):
        key, pay = carry
        tgt = idx_ref[j]
        dk = dkey_ref[j]
        hit = (rows == tgt) & (dk > key)
        drow = dpay_ref[pl.ds(j, 1), :]                              # [1, D]
        key = jnp.where(hit, dk, key)
        pay = jnp.where(hit[:, None], drow, pay)
        return key, pay

    key, pay = jax.lax.fori_loop(
        0, n_lanes, lane, (key_ref[...], pay_ref[...]))
    key_o_ref[...] = key
    pay_o_ref[...] = pay


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret"))
def delta_apply(key: jax.Array, pay: jax.Array, d_idx: jax.Array,
                d_key: jax.Array, d_pay: jax.Array, *, block_k: int = 1024,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """key: i32[K]; pay: [K, D]; d_idx/d_key: i32[Dc]; d_pay: [Dc, D].

    K, D, Dc already padded by ops.py (empty delta lanes hold idx = -1).
    """
    k_dim, d = pay.shape
    dc = d_idx.shape[0]
    grid = (k_dim // block_k,)
    key_spec = pl.BlockSpec((block_k,), lambda i: (i,))
    pay_spec = pl.BlockSpec((block_k, d), lambda i: (i, 0))
    lane_spec = pl.BlockSpec((dc,), lambda i: (0,),
                             memory_space=pltpu.SMEM)
    dpay_spec = pl.BlockSpec((dc, d), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_apply_kernel, block_k=block_k),
        grid=grid,
        in_specs=[lane_spec, lane_spec, dpay_spec, key_spec, pay_spec],
        out_specs=[key_spec, pay_spec],
        out_shape=[
            jax.ShapeDtypeStruct(key.shape, key.dtype),
            jax.ShapeDtypeStruct(pay.shape, pay.dtype),
        ],
        interpret=interpret,
    )(d_idx, d_key, d_pay, key, pay)
