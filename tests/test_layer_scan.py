"""The mixed step's layer scan hands its Pallas kernels whole layer stacks.

No kernel in the scan gets a scan slice of a stacked buffer: the page
pools ride the scan's carry as ``[L, P, ...]`` stacks, the held-expert
banks are scan constants, and each kernel addresses layer ``layer`` of its
stack.  Checked here three ways, on CPU (kernels in interpret mode):

  * each layer-indexed kernel on a 3-layer stack gives bit for bit what
    the same call gives on that layer alone, and touches no other layer;
  * the traced mixed step carries the pools and slices no pool or bank;
  * the mixed step through the kernels matches it through the oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels import moe_grouped_matmul as gmm
from repro.kernels import ops
from repro.models import cache as cache_mod
from repro.models import lm, moe

L, B, PS, MAXP, C = 3, 2, 8, 4, 4
P = B * MAXP + 1
START = jnp.asarray([3, 11], jnp.int32)      # row 0 straddles two pages
SPAN = jnp.asarray([4, 2], jnp.int32)


def _normal(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=shape), dtype)


def _table(rng):
    return jnp.asarray(rng.permutation(P)[:B * MAXP].reshape(B, MAXP),
                       jnp.int32)


def _quant_pool(rng, shape, dtype=jnp.int8):
    """A quantized pool and its per-row scales, through the oracle."""
    from repro.kernels import ref
    return ref.quantize_rows(_normal(rng, shape), dtype)


def _mha(rng, use_pallas):
    hq, hkv, d = 4, 2, 32
    q, kn, vn = (_normal(rng, (B, h, C, d)) for h in (hq, hkv, hkv))
    bt = _table(rng)
    pools = tuple(_normal(rng, (L, P, hkv, PS, d)) for _ in range(2))

    def run(layer, kp, vp):
        return ops.paged_chunk_attention(q, kp, vp, bt, START, SPAN, kn, vn,
                                         layer=layer, use_pallas=use_pallas)
    return run, pools


def _mha_quant(rng, use_pallas):
    hq, hkv, d = 4, 2, 32
    q, kn, vn = (_normal(rng, (B, h, C, d)) for h in (hq, hkv, hkv))
    bt = _table(rng)
    (kp, ks), (vp, vs) = (_quant_pool(rng, (L, P, hkv, PS, d))
                          for _ in range(2))

    def run(layer, kp, vp, ks, vs):
        return ops.paged_chunk_attention_quant(
            q, kp, ks, vp, vs, bt, START, SPAN, kn, vn, layer=layer,
            use_pallas=use_pallas)
    return run, (kp, vp, ks, vs)


def _mla(rng, use_pallas):
    h, r, rd, dp = 4, 16, 8, 128
    q_abs, q_rope = _normal(rng, (B, h, C, r)), _normal(rng, (B, h, C, rd))
    ln, bt = _normal(rng, (B, C, dp)), _table(rng)

    def run(layer, lp):
        return ops.paged_mla_chunk(q_abs, q_rope, lp, bt, START, SPAN, ln,
                                   layer=layer, scale=0.125,
                                   use_pallas=use_pallas)
    return run, (_normal(rng, (L, P, PS, dp)),)


def _mla_quant(rng, use_pallas):
    h, r, rd, dp = 4, 16, 8, 128
    q_abs, q_rope = _normal(rng, (B, h, C, r)), _normal(rng, (B, h, C, rd))
    ln, bt = _normal(rng, (B, C, dp)), _table(rng)

    def run(layer, lp, ls):
        return ops.paged_mla_chunk_quant(q_abs, q_rope, lp, ls, bt, START,
                                         SPAN, ln, layer=layer, scale=0.125,
                                         use_pallas=use_pallas)
    return run, _quant_pool(rng, (L, P, PS, dp))


def _grouped_interpreted(x, w_gate, w_up, w_down, tile_group, num_tiles, *,
                         tm, layer=None):
    """``ops.moe_grouped_swiglu`` as on a TPU, the kernel interpreted: one
    layer's banks (``layer`` None) go in as a stack of one."""
    if layer is None:
        w_gate, w_up, w_down, layer = (w_gate[None], w_up[None],
                                       w_down[None], 0)
    return gmm.moe_grouped_swiglu(
        x, w_gate, w_up, w_down, tile_group, num_tiles, tm=tm,
        layer=jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        interpret=True)


def _experts(rng, use_pallas):
    """The grouped SwiGLU reads its banks and writes no pool: its result
    is the output alone."""
    held, d, h, tm = 4, 32, 48, 16
    local = jnp.asarray(rng.permutation(np.repeat(np.arange(held),
                                                  [19, 0, 5, 16])),
                        jnp.int32)
    _, tg, nt, _ = moe.group_pairs(local, jnp.ones(local.shape, bool),
                                   held, tm)
    x = _normal(rng, (tg.shape[0] * tm, d))
    banks = tuple(_normal(rng, (L,) + sh) * 0.2
                  for sh in ((held, d, h), (held, d, h), (held, h, d)))
    grouped = _grouped_interpreted if use_pallas else ops.moe_grouped_swiglu

    def run(layer, *w):
        y = grouped(x, *w, tg, nt, tm=tm, layer=layer)
        return (y[:int(nt[0]) * tm],)           # rows of live tiles only
    return run, banks


KERNELS = {"paged_chunk_attention": (_mha, True),
           "paged_chunk_attention_quant": (_mha_quant, True),
           "paged_mla_chunk": (_mla, True),
           "paged_mla_chunk_quant": (_mla_quant, True),
           "moe_grouped_swiglu": (_experts, False)}


def _span_rows(out):
    """Outputs at each row's span positions (the rest is garbage)."""
    m = np.arange(C)[None, :] < np.asarray(SPAN)[:, None]          # [B, C]
    return np.where(m[:, None, :, None], np.asarray(out), 0)


@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_layer_indexed_kernel_equals_call_on_layer(kernel, layer, impl):
    build, writes_pools = KERNELS[kernel]
    run, stacks = build(np.random.default_rng(layer), impl == "pallas")
    got = run(jnp.int32(layer), *stacks)
    want = run(None, *(s[layer] for s in stacks))
    rows = _span_rows if writes_pools else np.asarray
    np.testing.assert_array_equal(rows(got[0]), rows(want[0]))
    if not writes_pools:
        return
    for stack, new, one in zip(stacks, got[1:], want[1:]):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[layer], np.asarray(one))
        assert not np.array_equal(np.asarray(one), np.asarray(stack[layer]))
        others = [i for i in range(L) if i != layer]
        np.testing.assert_array_equal(new[others],
                                      np.asarray(stack)[others])


# ---------------------------------------------------------------------------
# The traced mixed step
# ---------------------------------------------------------------------------

def _tiny(family):
    """(cfg, kv_quant): three scanned layers of each attention family."""
    if family == "mla_moe":
        cfg = configs.reduced(configs.get("deepseek-v2-lite-16b"),
                              d_model=32, vocab=128, layers=4)
        return cfg.replace(experts_first=0, experts_held=4), "off"
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128,
                          layers=3)
    if family == "gqa":
        cfg = cfg.replace(num_kv_heads=2)
    return cfg, ("int8" if family == "mha_int8" else "off")


def _inputs(cfg, kv_quant, dtype=jnp.float32):
    params = jax.tree.map(lambda x: x.astype(dtype),
                          lm.init(jax.random.PRNGKey(0), cfg))
    cache = lm.init_cache(cfg, B, MAXP * PS, dtype, paged=True,
                          page_size=PS, num_pages=P, kv_quant=kv_quant)
    cache = lm.set_block_tables(cache, _table(np.random.default_rng(0)))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 128, (B, C)),
                         jnp.int32)
    return params, cache, tokens


def _path_names(path):
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


@pytest.mark.parametrize("family", ["mha", "gqa", "mha_int8", "mla_moe"])
def test_mixed_step_scan_carries_pools_and_slices_no_pool_or_bank(family):
    cfg, kv_quant = _tiny(family)
    params, cache, tokens = _inputs(cfg, kv_quant)
    assert cfg.pattern_groups == 3
    closed = jax.make_jaxpr(
        lambda p, c, t, s, sp: lm.mixed_step(p, cfg, t, c, s, sp))(
        params, cache, tokens, START, SPAN)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        (params, cache, tokens, START, SPAN))
    pools, banks = [], []
    for (path, leaf), var in zip(flat, closed.jaxpr.invars):
        names = _path_names(path)
        if names[0] == "1" and names[1] == "groups":
            layout = cache_mod.layout_of(cache["groups"][names[2]])
            if names[3] in cache_mod.pool_leaves(None, layout):
                pools.append((var, leaf.shape))
        if names[0] == "0" and "experts" in names:
            banks.append((var, leaf.shape))
    assert len(pools) == (4 if family == "mha_int8" else
                          1 if family == "mla_moe" else 2)
    assert len(banks) == (3 if family == "mla_moe" else 0)

    scan, = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    nc, ncarry = scan.params["num_consts"], scan.params["num_carry"]
    consts = scan.invars[:nc]
    carry = scan.invars[nc:nc + ncarry]
    xs = scan.invars[nc + ncarry:]
    ys = scan.outvars[ncarry:]
    for var, _ in pools:
        assert any(var is v for v in carry)
        assert not any(var is v for v in xs)
    for var, _ in banks:
        assert any(var is v for v in consts)
        assert not any(var is v for v in xs)
    # Nothing the size of a pool or bank stack goes through xs or ys.
    big = {shape for _, shape in pools + banks}
    for v in list(xs) + list(ys):
        assert tuple(v.aval.shape) not in big


@pytest.mark.parametrize("family", ["mha", "gqa", "mla_moe"])
def test_mixed_step_kernels_match_oracles_through_the_layer_scan(
        family, monkeypatch):
    """Two mixed steps (a prompt chunk, then a decode token per row)
    through the interpreted kernels and through the oracles: the same
    logits and pools to float32 rounding (a layer's K/V carry the
    rounding of the attention below it).  Every row
    fills its width: the kernels' outputs at padded positions are
    garbage, which the interpreter may fill with NaN."""
    cfg, kv_quant = _tiny(family)
    params, cache, tokens = _inputs(cfg, kv_quant)
    grouped = ops.moe_grouped_swiglu

    def step(impl, cache):
        if impl == "pallas":            # the grouped kernel too, interpreted
            monkeypatch.setattr(ops, "moe_grouped_swiglu",
                                _grouped_interpreted)
        else:
            monkeypatch.setattr(ops, "moe_grouped_swiglu", grouped)
        out = []
        full = jnp.full((B,), C, jnp.int32)
        for toks, start, span in ((tokens, START, full),
                                  (tokens[:, :1], START + full,
                                   jnp.ones((B,), jnp.int32))):
            logits, cache, _ = lm.mixed_step(params, cfg, toks, cache,
                                             start, span, impl=impl)
            out.append(np.asarray(logits))
        return out, cache

    got, got_cache = step("pallas", cache)
    want, want_cache = step("ref", cache)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    for path, layout, layer in cache_mod.iter_layers(want_cache):
        other = got_cache
        for k in path:
            other = other[k]
        for name in cache_mod.pool_leaves(layer, layout):
            np.testing.assert_allclose(np.asarray(other[name]),
                                       np.asarray(layer[name]),
                                       rtol=2e-4, atol=2e-4)
