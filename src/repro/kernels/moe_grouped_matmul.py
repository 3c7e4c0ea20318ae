"""Grouped SwiGLU over the routed experts a chip holds (Pallas, TPU).

The expert layer (models/moe.py) lays its live (token, expert) pairs out
grouped by held expert, each group starting on a tile boundary: rows
``[t*tm, (t+1)*tm)`` of ``x`` belong to tile ``t`` and all of a tile's rows
to one expert, ``tile_group[t]``.  Rows past a group's end are padding.
Only the first ``num_tiles`` tiles hold pairs; the grid is that long
(a dynamic grid, read from the scalar-prefetched ``num_tiles``), so
neither padding tiles nor experts that no pair chose cost work or bytes.

Per tile, over d_expert in blocks of ``th`` (the inner grid axis):

  acc += (silu(x @ Wg[:, blk]) * (x @ Wu[:, blk])).astype(bf16) @ Wd[blk]

with f32 accumulation, written once at the last block.  Weight blocks are
indexed by the tile's expert, so an expert whose pairs fit one tile is
read exactly once per call.  Rows of tiles past ``num_tiles`` are left
unwritten: the caller reads only rows it placed pairs in.  The design
follows the public megablox ``gmm`` (jax.experimental.pallas.ops.tpu),
with group-aligned tiles instead of shared boundary tiles and the three
matmuls of the expert fused.

The banks arrive as the stacks of every layer, ``[L, G, d, h]``, with the
layer index in the scalar prefetch (``layer``, i32[1]): the weight blocks'
index maps address that layer of the stack, so a layer scan closes over
the stacks and no step slices a layer's bank out of them.  A caller that
holds one layer's bank (the trainer's scan) passes a stack of one
(``kernels.ops`` does: the leading axis is a free reshape).

The kernel is forward only (Pallas cannot differentiate a dynamic grid or
scalar-prefetched operands).  Its VJP recomputes the layer with
``jax.lax.ragged_dot`` over the same layout, where the groups are the
live tiles' experts in order (XLA's grouped matmul on a TPU, which reads
each expert's weights in place), so the trainer differentiates through it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM held by the weight blocks of one grid step (three [d, th] bf16
# blocks, double-buffered) stays under this.
_WEIGHT_VMEM = 8 * 1024 * 1024


def hidden_block(d: int, h: int, itemsize: int = 2) -> int:
    """The d_expert block: the largest multiple of 128 dividing ``h`` whose
    three weight blocks fit the VMEM budget twice (``h`` itself when it is
    not a multiple of 128: the interpreter's small shapes)."""
    if h % 128:
        return h
    best = 128
    for th in range(128, h + 1, 128):
        if h % th == 0 and 2 * 3 * d * th * itemsize <= _WEIGHT_VMEM:
            best = th
    return best


def _kernel(layer_ref, tg_ref, nt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(a, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_grouped_swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                       w_down: jax.Array, tile_group: jax.Array,
                       num_tiles: jax.Array, *, tm: int, layer: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """x: [T*tm, d] pair rows in group-aligned tiles; w_gate / w_up:
    [L, G, d, h] and w_down: [L, G, h, d] stacks addressed at ``layer``
    (i32[1]); tile_group: i32[T] (expert of each tile, non-decreasing over
    the live tiles); num_tiles: i32[1] live tiles.  Returns [T*tm, d] in
    x's dtype, rows of live tiles only (the rest unwritten)."""
    return _grouped(x, w_gate, w_up, w_down, tile_group, num_tiles, layer,
                    tm, interpret)


def ragged_swiglu(x, w_gate, w_up, w_down, tile_group, num_tiles, *,
                  tm: int) -> jax.Array:
    """The same layer through ``jax.lax.ragged_dot``: each expert's rows
    are its live tiles, contiguous and in expert order; rows past the live
    tiles give 0.  Same rounding as the kernel."""
    groups = w_gate.shape[0]
    live = jnp.arange(tile_group.shape[0]) < num_tiles[0]
    sizes = tm * jnp.sum((tile_group[:, None] == jnp.arange(groups))
                         & live[:, None], axis=0, dtype=jnp.int32)
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=jnp.float32)
    a = (jax.nn.silu(dot(x, w_gate)) * dot(x, w_up)).astype(x.dtype)
    return dot(a, w_down).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _grouped(x, w_gate, w_up, w_down, tile_group, num_tiles, layer, tm,
             interpret):
    return _forward(x, w_gate, w_up, w_down, tile_group, num_tiles, layer,
                    tm, interpret)


def _grouped_fwd(x, w_gate, w_up, w_down, tile_group, num_tiles, layer, tm,
                 interpret):
    y = _forward(x, w_gate, w_up, w_down, tile_group, num_tiles, layer, tm,
                 interpret)
    return y, (x, w_gate, w_up, w_down, tile_group, num_tiles, layer)


def _grouped_bwd(tm, interpret, res, ct):
    x, w_gate, w_up, w_down, tile_group, num_tiles, layer = res
    _, vjp = jax.vjp(lambda x, *w: ragged_swiglu(
        x, *(b[layer[0]] for b in w), tile_group, num_tiles, tm=tm),
        x, w_gate, w_up, w_down)
    return (*vjp(ct), None, None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _forward(x, w_gate, w_up, w_down, tile_group, num_tiles, layer, tm,
             interpret):
    rows, d = x.shape
    h = w_gate.shape[-1]
    th = hidden_block(d, h, w_gate.dtype.itemsize)
    if rows % tm:
        raise ValueError(f"{rows} pair rows are not whole tiles of {tm}")

    # Weight blocks of the tile's expert, in layer ``layer`` of the stacks.
    up = pl.BlockSpec((None, None, d, th),
                      lambda t, j, lyr, tg, nt: (lyr[0], tg[t], 0, j))
    down = pl.BlockSpec((None, None, th, d),
                        lambda t, j, lyr, tg, nt: (lyr[0], tg[t], j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # layer, tile_group, num_tiles
        grid=(num_tiles[0], h // th),
        in_specs=[
            pl.BlockSpec((tm, d), lambda t, j, lyr, tg, nt: (t, 0)),
            up, up, down,
        ],
        out_specs=pl.BlockSpec((tm, d), lambda t, j, lyr, tg, nt: (t, 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(layer, tile_group, num_tiles, x, w_gate, w_up, w_down)
