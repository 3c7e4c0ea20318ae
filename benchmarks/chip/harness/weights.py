"""Random weights from ``--seed``, made by the benchmark on the device.

The program supplies only the shape of its parameter tree (``lm.init``
under ``jax.eval_shape``, which allocates nothing); the values come from
here, in one jitted call, in the served dtype.  Matrices are normal with
standard deviation fan_in^-1/2 (the embedding d^-1/2, so tied logits have
unit scale); biases and LayerNorm weights are drawn too, not left at 0 and
1, so a path that dropped them would show in the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _draw(key, name: str, path: str, shape, dtype):
    if name == "w":
        fan_in = shape[-1] if path.startswith("embed") else shape[-2]
        x = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    elif name in ("b", "bias"):
        x = jax.random.normal(key, shape, jnp.float32) * 0.05
    elif name == "scale":
        x = 1.0 + jax.random.normal(key, shape, jnp.float32) * 0.1
    else:
        raise ValueError(f"no rule to draw parameter {path}")
    return x.astype(dtype)


def make(abstract, seed: int):
    """Parameters shaped like ``abstract`` (a tree of ShapeDtypeStructs).
    Leaves stacked over layers are drawn one layer at a time, so no
    float32 temporary of a whole stack is ever live."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(key):
        out = []
        for i, (kp, leaf) in enumerate(flat):
            names = [str(getattr(k, "key", k)) for k in kp]
            path, name = "/".join(names), names[-1]
            k = jax.random.fold_in(key, i)
            if names[0] == "groups":
                keys = jax.random.split(k, leaf.shape[0])
                out.append(jax.lax.map(
                    lambda kk, s=leaf.shape[1:], n=name, p=path, d=leaf.dtype:
                    _draw(kk, n, p, s, d), keys))
            else:
                out.append(_draw(k, name, path, leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    seed = int(seed) % (1 << 63)
    return jax.block_until_ready(jax.jit(build)(jax.random.key(seed)))
