#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU.

One chip (the default):

  * builds olmo-1b at its published widths (``configs/olmo_1b.py``: 16
    layers, d_model 2048, 16 MHA heads, head_dim 128, d_ff 8192, vocab
    50304) with random weights from ``--seed``;
  * compares the logits (every span position) and written page pools of
    two mixed steps on the Pallas kernels against the jnp reference path
    (``impl="ref"``) on the same chip, within ``TOL``, and checks that a
    planted one-slot fault fails that comparison;
  * serves ``N_REQUESTS`` requests (more than the batch; two share a
    two-page prompt prefix) through ``ContinuousBatchingEngine`` with bf16
    page pools, page size 64, chunked admission and prefix sharing, on the
    kernel path the platform resolves (``kernels.ops.default_impl``), and
    checks that the compiled mixed step holds a Mosaic kernel
    (``tpu_custom_call``) and that every request completes.

``--chips 4`` runs only the collective phase: olmo-1b replicated on a
(data=4, model=1) mesh, a few fused decode+merge steps under the ``pmax``
and ``delta`` merges, a check that the coordination state lives on four
distinct devices, and a check that both merges give documents bit-identical
to a single-device ``fold_join`` of the same per-replica states.

The lines before the last are smoke output, not benchmark numbers.  The
last line is one JSON object naming the device.  Without a TPU the script
exits non-zero and prints no result: there is no CPU fallback.

  python chip_smoke.py [--seed N]
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

BATCH = 8
PAGE_SIZE = 64
CHUNK = 128
MAX_LEN = 2048
N_REQUESTS = 12
SHARED_PREFIX = 2 * PAGE_SIZE
# Largest |Pallas - reference| gap accepted, as a fraction of the
# reference's largest magnitude (floor 1.0), for the logits at every span
# position and for the written page pools.  Both paths keep bf16
# activations, weights and pools; a bf16 rounding is 2^-9 relative, and 16
# layers of differently ordered f32 accumulation stay inside 3e-2.  A
# planted fault (the reference's decode step written and read one slot
# late) must fail the same comparison, or the check itself fails.
TOL = 3e-2


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def require_tpu(chips: int):
    """The devices this run drives; raises SmokeFailure without a TPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:          # JAX_PLATFORMS names a missing backend
        raise SmokeFailure(f"no TPU found: {e}") from None
    check(devices[0].platform == "tpu",
          f"no TPU found: JAX sees platform {devices[0].platform!r}")
    check(len(devices) >= chips,
          f"{chips} chips requested, JAX sees {len(devices)}")
    return devices[:chips] if chips > 1 else devices


def init_params(cfg, seed: int, shardings=None):
    import jax
    from repro.models import lm
    init = jax.jit(functools.partial(lm.init, cfg=cfg),
                   out_shardings=shardings)
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


# ---------------------------------------------------------------------------
# One chip: Pallas vs reference logits, then the engine
# ---------------------------------------------------------------------------

def logit_phase(cfg, params, seed: int) -> None:
    """Two mixed steps (a full admission chunk, then decode, partial and
    idle spans) on both kernel paths from identical caches.  Checks the
    logits at every valid span position and the written pools against
    ``TOL``, then checks that a planted one-slot fault fails that check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.models import attention, lm

    check(ops.default_impl() == "pallas",
          f"platform resolved impl={ops.default_impl()!r}, not the kernels")

    rng = np.random.default_rng(seed)
    steps = [   # (tokens, start, span)
        (rng.integers(0, cfg.vocab_size, (BATCH, CHUNK)),
         np.zeros(BATCH, np.int32), np.full(BATCH, CHUNK, np.int32)),
        # decode, full chunk, page-straddling, idle and short spans
        (rng.integers(0, cfg.vocab_size, (BATCH, CHUNK)),
         np.full(BATCH, CHUNK, np.int32),
         np.array([1, CHUNK, 37, 0, 64, 100, 1, 5], np.int32)),
    ]
    pool_gap = jax.jit(lambda a, b: jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
    pool_max = jax.jit(lambda a: jnp.max(jnp.abs(a.astype(jnp.float32))))

    def run(impl, shift=0):
        """Logits at every valid span position and the cache afterwards;
        ``shift`` moves the second step's span that many slots late."""
        fn = jax.jit(lambda p, c, t, s, n: lm.mixed_step(
            p, cfg, t, c, s, n, impl=impl, all_logits=True),
            donate_argnums=(1,))
        cache = lm.set_block_tables(
            lm.init_cache(cfg, BATCH, MAX_LEN, paged=True,
                          page_size=PAGE_SIZE),
            attention.default_block_tables(BATCH, MAX_LEN, PAGE_SIZE))
        outs = []
        for i, (toks, start, span) in enumerate(steps):
            start = start + (shift if i == 1 else 0)
            out, cache, _ = fn(params, cache, jnp.asarray(toks, jnp.int32),
                               jnp.asarray(start), jnp.asarray(span))
            valid = np.arange(CHUNK)[None, :] < span[:, None]
            outs.append(np.asarray(out)[valid])
        return np.concatenate(outs), cache

    def gaps(logits, cache, ref_logits, ref_cache):
        """(logit gap, pool gap), each as a fraction of the reference's
        largest magnitude (floor 1.0)."""
        lg = float(np.max(np.abs(logits - ref_logits)))
        lg /= max(1.0, float(np.max(np.abs(ref_logits))))
        pg = max(float(pool_gap(a, b)) / max(1.0, float(pool_max(b)))
                 for a, b in zip(jax.tree.leaves(cache),
                                 jax.tree.leaves(ref_cache)))
        return lg, pg

    p_logits, p_cache = run("pallas")
    r_logits, r_cache = run("ref")
    check(bool(np.isfinite(p_logits).all() and np.isfinite(r_logits).all()),
          "non-finite logits")
    lg, pg = gaps(p_logits, p_cache, r_logits, r_cache)
    del p_cache
    say("logit_positions_compared", p_logits.shape[0])
    say("logit_ref_max_abs", float(np.max(np.abs(r_logits))))
    say("logit_gap_rel", lg)
    say("pool_gap_rel", pg)
    say("gap_tol_rel", TOL)
    say("logit_argmax_agree",
        float(np.mean(p_logits.argmax(-1) == r_logits.argmax(-1))))
    check(lg <= TOL and pg <= TOL,
          f"Pallas differs from the reference: logits by {lg}, pools by "
          f"{pg} of the reference's scale (bound {TOL})")

    f_logits, f_cache = run("ref", shift=1)
    flg, fpg = gaps(f_logits, f_cache, r_logits, r_cache)
    del f_cache, r_cache
    say("planted_fault_logit_gap_rel", flg)
    say("planted_fault_pool_gap_rel", fpg)
    check(flg > TOL or fpg > TOL,
          "a span written one slot late passes the comparison")


def make_requests(cfg, seed: int):
    import numpy as np
    from repro.serving.scheduler import Request

    rng = np.random.default_rng(seed + 1)
    shared = rng.integers(0, cfg.vocab_size, SHARED_PREFIX).tolist()
    reqs = []
    for rid in range(N_REQUESTS):
        # Prompts are whole chunks so the mixed step runs at two widths
        # (CHUNK while admitting, 1 while only decoding).
        n = CHUNK * int(rng.integers(1, 4))
        prompt = rng.integers(0, cfg.vocab_size, n).tolist()
        if rid < 2:
            prompt = shared + prompt[:CHUNK]
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new_tokens=int(rng.integers(8, 33))))
    return reqs


def serve_phase(cfg, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serving.scheduler import COMPLETED, ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(
        cfg, params, batch=BATCH, max_len=MAX_LEN, page_size=PAGE_SIZE,
        chunk_size=CHUNK, prefix_sharing=True, seed=seed)
    t0 = time.perf_counter()
    compiled = eng._mixed.lower(
        params, eng.cache, jnp.zeros((BATCH, CHUNK), jnp.int32),
        jnp.zeros((BATCH,), jnp.int32), jnp.zeros((BATCH,), jnp.int32),
        jax.random.PRNGKey(0)).compile()
    say("mixed_step_compile_s", time.perf_counter() - t0)
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled mixed step holds no tpu_custom_call")
    say("mixed_step_has_tpu_custom_call", True)

    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    eng.run(reqs, max_steps=2000)
    wall = time.perf_counter() - t0
    st = eng.stats
    say("requests", f"{len(reqs)} submitted, batch {BATCH}")
    say("steps", st["steps"])
    say("generated_tokens", st["gen_tokens"])
    say("prefill_chunks", st["prefill_chunks"])
    say("shared_pages", st["shared_pages"])
    say("serve_wall_s", wall)
    stats = jax.devices()[0].memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    done = [r for r in reqs if r.status == COMPLETED
            and len(r.tokens) == r.max_new_tokens]
    check(len(done) == len(reqs),
          f"{len(reqs) - len(done)} of {len(reqs)} requests did not complete")
    check(all(0 <= t < cfg.vocab_size for r in reqs for t in r.tokens),
          "a generated token is outside the vocabulary")
    check(st["gen_tokens"] == sum(r.max_new_tokens for r in reqs),
          "generated-token count disagrees with the requests")
    check(st["shared_pages"] > 0, "prefix sharing never shared a page")
    check(st["prefill_chunks"] > len(reqs),
          "no prompt was admitted in more than one chunk")


# ---------------------------------------------------------------------------
# Four chips: fused decode + CRDT merge across replicas
# ---------------------------------------------------------------------------

def collective_phase(cfg, seed: int, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import doc as doc_mod, gset, merge as merge_mod
    from repro.models import lm
    from repro.serving import engine as engine_mod
    from repro.sharding.partition import Partitioner

    n_rep = len(devices)
    mesh = Mesh(np.asarray(devices).reshape(n_rep, 1), ("data", "model"))
    dp = ("data",)
    part = Partitioner(mesh, fsdp=False)
    params = init_params(cfg, seed, part.params_shardings(
        lm.abstract_params(cfg)))
    batch, max_len, merge_every, n_steps = 2 * n_rep, 256, 4, 7
    rows = NamedSharding(mesh, P(dp))

    def sharded(tree):
        return jax.device_put(tree, jax.tree.map(
            lambda x: NamedSharding(mesh, P(dp, *([None] * (x.ndim - 1)))),
            tree))

    digests = {}
    for strategy in ("pmax", "delta"):
        base = {"doc": doc_mod.empty(batch, 64),
                "heartbeats": gset.GCounter.zeros(n_rep)}
        if strategy == "delta":
            base = engine_mod.with_delta_frontier(base)
        coord = sharded(engine_mod.replicate_coord(base, n_rep))
        cache_abs = jax.eval_shape(lambda: lm.init_cache(cfg, batch, max_len))
        cache = jax.jit(lambda: lm.init_cache(cfg, batch, max_len),
                        out_shardings=part.cache_shardings(cache_abs))()
        # One GSPMD program over the mesh: its attention runs the jnp path
        # (make_fused_serve_step's default), as XLA cannot partition a
        # Mosaic kernel.
        step = jax.jit(engine_mod.make_fused_serve_step(
            cfg, mesh, dp, merge_strategy=strategy,
            merge_every=merge_every), donate_argnums=(1,))
        token = jax.device_put(jnp.full((batch,), 3, jnp.int32), rows)
        pos = jax.device_put(jnp.zeros((batch,), jnp.int32), rows)
        slots = jax.device_put(jnp.arange(batch, dtype=jnp.int32), rows)
        active = jax.device_put(jnp.ones((batch,), bool), rows)
        with mesh:
            for t in range(1, n_steps + 1):   # merges at t = merge_every only
                token, cache, pos, coord = step(params, cache, token, pos,
                                                slots, active, coord,
                                                jnp.int32(t))
        jax.block_until_ready(coord)
        for leaf in jax.tree.leaves(coord):
            placed = {s.device for s in leaf.addressable_shards}
            check(len(placed) == n_rep,
                  f"{strategy}: a coordination leaf sits on {len(placed)} "
                  f"devices, not {n_rep}")
        say(f"{strategy}_coord_devices", n_rep)

        # Replicas diverged for the steps after the last merge; the
        # collective merge must equal a one-device join of those states.
        state = {k: v for k, v in coord.items() if k != "frontier"}
        host = jax.tree.map(np.asarray, state)
        per_replica = [jax.device_put(jax.tree.map(lambda x: x[r], host),
                                      devices[0]) for r in range(n_rep)]
        want = jax.tree.map(np.asarray, merge_mod.fold_join(per_replica))
        check(any(not np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(jax.tree.map(lambda x: x[0], host)),
            jax.tree.leaves(want))), f"{strategy}: replicas never diverged")
        merged = jax.jit(engine_mod.make_coord_merge(
            mesh, dp, strategy))(coord)
        got = jax.tree.map(np.asarray,
                           {k: v for k, v in merged.items()
                            if k != "frontier"})
        for r in range(n_rep):
            for g, w in zip(jax.tree.leaves(jax.tree.map(lambda x: x[r],
                                                         got)),
                            jax.tree.leaves(want)):
                check(np.array_equal(g, w),
                      f"{strategy}: replica {r} differs from fold_join")
        digests[strategy] = int(doc_mod.digest(
            jax.tree.map(jnp.asarray, want["doc"])))
        say(f"{strategy}_merged_equals_fold_join", f"{n_rep} replicas")
        say(f"{strategy}_doc_digest", digests[strategy])
        say(f"{strategy}_doc_tokens",
            int(np.asarray(want["doc"].length).sum()))
    check(digests["pmax"] == digests["delta"],
          "pmax and delta merged different documents")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip collective phase")
    args = ap.parse_args()
    try:
        if not (SRC / "repro").is_dir():
            raise SmokeFailure(f"the repro package is missing ({SRC})")
        sys.path.insert(0, str(SRC))
        from repro.runtime import compile_cache
        cache_dir = compile_cache.enable()
        devices = require_tpu(args.chips)
        say("compile_cache_dir", cache_dir)
        import repro.configs as configs
        cfg = configs.get("olmo-1b")
        say("model", f"{cfg.name} layers={cfg.num_layers} "
            f"d_model={cfg.d_model} heads={cfg.num_heads} "
            f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
            f"vocab={cfg.vocab_size}")
        if args.chips == 4:
            collective_phase(cfg, args.seed, devices)
        else:
            params = init_params(cfg, args.seed)
            logit_phase(cfg, params, args.seed)
            serve_phase(cfg, params, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
