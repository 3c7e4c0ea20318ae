"""Parameter/activation partitioner: param-tree path -> PartitionSpec.

Megatron-style tensor parallelism on the ``model`` axis, (pod×)data
parallelism on the batch dims, expert parallelism for MoE banks, with
divisibility guards (a dim that doesn't divide the mesh axis is replicated —
e.g. MQA kv projections with one head, whisper's odd vocab).

Layer-stacked ("groups") params carry a leading scan dim that is never
sharded.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Any


class Partitioner:
    def __init__(self, mesh: Mesh, *, model_axis: str = "model",
                 fsdp: bool = True, mla_cache: str = "latent"):
        self.mesh = mesh
        self.model_axis = model_axis
        self.model_size = mesh.shape[model_axis]
        # Batch shards over every non-model axis ("pod" included if present).
        self.batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)
        self.data_size = 1
        for a in self.batch_axes:
            self.data_size *= mesh.shape[a]
        # FSDP: additionally shard each parameter's largest remaining dim over
        # the data axes (params are all-gathered per layer inside the scan;
        # grads reduce-scatter back — the standard fully-sharded schedule).
        self.fsdp = fsdp
        # §Perf variants for the MLA latent cache sharding:
        #   "latent"     shard r over model (baseline; logits all-reduce)
        #   "replicated" replicate (no collectives; full cache read/device)
        #   "seq"        shard S over model (local r-contraction, partial
        #                softmax with tiny [B,H] reductions — the winner)
        self.mla_cache = mla_cache

    # -- helpers -----------------------------------------------------------

    def _m(self, dim_size: int):
        """'model' if it divides, else replicated."""
        return self.model_axis if dim_size % self.model_size == 0 else None

    def batch_spec(self, extra_dims: int = 1) -> P:
        return P(self.batch_axes, *([None] * extra_dims))

    # -- parameter rules ---------------------------------------------------

    _COL = {"wq", "wk", "wv", "gate", "up", "in_gate", "in_rec", "up_mlstm",
            "up_gate", "w_in", "w_q", "w_uk", "w_uv", "head", "gate_i",
            "gate_r", "conv_w"}
    _ROW = {"wo", "down", "out", "w_o", "xattn_out"}
    _REPL = {"router", "w_dkv", "w_kr", "w_if", "kv_norm"}

    def param_spec(self, path: tuple[str, ...], shape: tuple[int, ...]) -> P:
        stacked = "groups" in path or "blocks" in path
        core = shape[1:] if stacked else shape
        base = self._base_spec(path, core)
        if self.fsdp and len(core) >= 2:
            base = self._fsdpify(base, core)
        if stacked:
            base = P(None, *base)
        assert len(base) <= len(shape), (path, shape, base)
        return base

    def _fsdpify(self, spec: P, shape) -> P:
        parts = list(spec) + [None] * (len(shape) - len(spec))
        # Largest replicated, divisible dim gets the data axes.
        best, best_dim = None, 0
        for i, (ax, dim) in enumerate(zip(parts, shape)):
            if ax is None and dim % self.data_size == 0 and dim > best_dim:
                best, best_dim = i, dim
        if best is not None:
            parts[best] = self.batch_axes
        return P(*parts)

    def _base_spec(self, path: tuple[str, ...], shape) -> P:
        name = path[-1]
        parent = path[-2] if len(path) > 1 else ""
        grandparent = path[-3] if len(path) > 2 else ""

        if name == "w" and parent == "embed":
            return P(self._m(shape[0]), None)            # vocab rows
        if parent in self._REPL or name in self._REPL:
            return P(*([None] * len(shape)))
        # MoE expert banks ({gate,up,down}.w [E, ., .]): expert-parallel
        # on dim 0; shared experts split their hidden width.
        if grandparent == "experts":
            return P(self._m(shape[0]), None, None)
        if grandparent == "shared":
            if parent == "down":
                return P(None, self._m(shape[1]), None)
            return P(None, None, self._m(shape[2]))
        if name == "r" and len(shape) == 3:               # sLSTM recurrent
            return P(self._m(shape[0]), None, None)
        if name == "log_lambda":
            return P(self._m(shape[0]))
        if name == "conv_w":
            return P(None, self._m(shape[1]))
        if name == "w":
            key = parent
            if key in self._COL:
                return P(None, self._m(shape[1]))
            if key in self._ROW:
                return P(self._m(shape[0]), None)
        if name == "b":
            if parent in self._COL:
                return P(self._m(shape[0]))
            return P(None)
        # norms, pos embeddings, scalars: replicated.
        return P(*([None] * len(shape)))

    def params_specs(self, params: Params) -> Params:
        def spec(path, leaf):
            keys = tuple(_key_str(k) for k in path)
            return self.param_spec(keys, np.shape(leaf))

        return jax.tree_util.tree_map_with_path(spec, params)

    def params_shardings(self, params: Params) -> Params:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.params_specs(params))

    # -- cache rules (serving) ---------------------------------------------

    def cache_entry_spec(self, path: tuple[str, ...], shape,
                         *, shard_batch: bool, stacked: bool) -> P:
        """KV/recurrent cache sharding.

        Preference order per entry: shard heads on 'model' when divisible;
        otherwise shard the sequence axis (flash-decode handles partial
        softmax); batch on the data axes when divisible (long_500k has
        batch=1 -> sequence sharding carries the parallelism).
        """
        name = path[-1]
        core = shape[1:] if stacked else shape
        b_ax = self.batch_axes if shard_batch else None
        # Paged pools are shared across rows: dim 0 is pages, NOT batch —
        # never sharded over the data axes.  MHA pools shard over heads,
        # MLA latent pools over the latent-feature axis; block tables (and
        # the tiny coordination frontiers they travel with) replicate so
        # every device can walk any row's pages.
        if name in ("k_pages", "v_pages"):                # [P, Hkv, ps, D]
            spec = P(None, self._m(core[1]), None, None)
            return P(None, *spec) if stacked else spec
        if name == "latent_pages":                        # [P, ps, Dp]
            spec = P(None, None, self._m(core[2]))
            return P(None, *spec) if stacked else spec
        if name == "block_tables":                        # [B, maxp]
            spec = P(None, None)
            return P(None, *spec) if stacked else spec
        if name in ("k", "v", "xk", "xv"):                # [B, Hkv, S, D]
            h_ax = self._m(core[1])
            s_ax = self._m(core[2]) if h_ax is None else None
            spec = P(b_ax, h_ax, s_ax, None)
        elif name in ("ckv", "krope"):                    # [B, S, r]
            if self.mla_cache == "seq":
                spec = P(b_ax, self._m(core[1]), None)
            elif self.mla_cache == "replicated":
                spec = P(b_ax, None, None)
            else:                                          # "latent"
                spec = P(b_ax, None, self._m(core[2]))
        elif name == "C":                                 # [B, H, dh, dh]
            spec = P(b_ax, self._m(core[1]), None, None)
        elif name in ("h", "n", "c", "m") and len(core) == 2:
            spec = P(b_ax, self._m(core[1]))
        elif name == "conv":                              # [B, cw-1, W]
            spec = P(b_ax, None, self._m(core[2]))
        elif name in ("n",) and len(core) == 3:           # mLSTM n [B,H,dh]
            spec = P(b_ax, self._m(core[1]), None)
        elif name == "m" and len(core) == 2:
            spec = P(b_ax, None)
        else:
            spec = P(b_ax, *([None] * (len(core) - 1)))
        if stacked:
            spec = P(None, *spec)
        return spec

    def cache_shardings(self, cache: Params, *, shard_batch: bool = True
                        ) -> Params:
        def spec(path, leaf):
            keys = tuple(_key_str(k) for k in path)
            stacked = "groups" in keys
            return NamedSharding(
                self.mesh,
                self.cache_entry_spec(keys, np.shape(leaf),
                                      shard_batch=shard_batch,
                                      stacked=stacked))

        return jax.tree_util.tree_map_with_path(spec, cache)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "name"):
        return str(k.name)
    return str(getattr(k, "idx", k))
