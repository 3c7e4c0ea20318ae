"""Pallas TPU kernel: batched cross-pool page-row transfer (gather-scatter).

The disaggregated serving path moves *physical* page bytes between two
engines' pools: a prefill replica fills pages and publishes them; a decode
replica adopts the bytes instead of recomputing the prefix.  Per transfer a
batch of page rows moves ``src_pool[src_ids[i]] -> dst_pool[dst_ids[i]]``.

The copy is pure DMA — no compute touches the rows, so the transfer is
bitwise for every pool dtype (bf16/f32 KV rows, int8/fp8 quantized rows,
f32 scale rows) by construction.  Each grid program stages one page row
HBM -> VMEM -> HBM with double-buffered DMA so lane i+1's read overlaps
lane i's write.  Both pools are ANY-space (HBM) refs; the destination pool
is aliased input -> output, so XLA updates it in place and the moved rows
are the only destination bytes that change.

Negative ids drop the lane (same semantics as ``cache.copy_pages`` and the
oracle's mode="drop" scatter), so callers pad the transfer batch to a fixed
width with -1 and keep one compiled kernel per pool shape.

Alignment: on real TPU the pool row must be tileable (the ops wrapper
validates page_size against the dtype's sublane count and the trailing dim
against the 128-lane width); off-TPU the kernel runs in interpret mode at
any shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(src_ref, dst_ref, src_pool_in, dst_pool_in, dst_pool, buf, sem,
            *, num_src: int, num_dst: int):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    slot = jax.lax.rem(i, 2)

    def row_read(lane, buf_slot):
        pg = jnp.clip(src_ref[lane], 0, num_src - 1)
        return pltpu.make_async_copy(
            src_pool_in.at[pl.ds(pg, 1)], buf.at[pl.ds(buf_slot, 1)],
            sem.at[buf_slot])

    def lane_live(lane):
        return (src_ref[lane] >= 0) & (dst_ref[lane] >= 0) \
            & (dst_ref[lane] < num_dst)

    # Lane 0's read is issued by program 0; every later program issued its
    # own read as the "prefetch" of the previous program, so steady state
    # overlaps lane i's write-back with lane i+1's read.
    @pl.when((i == 0) & lane_live(0))
    def _first():
        row_read(0, 0).start()

    @pl.when((i + 1 < n) & lane_live(i + 1))
    def _prefetch():
        row_read(i + 1, jax.lax.rem(i + 1, 2)).start()

    @pl.when(lane_live(i))
    def _move():
        row_read(i, slot).wait()
        dst = dst_ref[i]
        wr = pltpu.make_async_copy(
            buf.at[pl.ds(slot, 1)], dst_pool.at[pl.ds(dst, 1)],
            sem.at[slot])
        wr.start()
        wr.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_transfer(src_pool: jax.Array, dst_pool: jax.Array,
                  src_ids: jax.Array, dst_ids: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """src_pool: [Ps, ...row]; dst_pool: [Pd, ...row] (same row shape and
    dtype); src_ids/dst_ids: i32[N] (lane i copies row src_ids[i] into row
    dst_ids[i]; -1 on either side drops the lane).  Returns the updated
    destination pool (in place on TPU via aliasing)."""
    n = src_ids.shape[0]
    row = src_pool.shape[1:]
    kernel = functools.partial(_kernel, num_src=src_pool.shape[0],
                               num_dst=dst_pool.shape[0])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # src_ids, dst_ids
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2,) + row, src_pool.dtype),     # staging double-buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        # Input indices count the scalar-prefetch operands (0, 1).
        input_output_aliases={3: 0},
        interpret=interpret,
    )(src_ids, dst_ids, src_pool, dst_pool)
