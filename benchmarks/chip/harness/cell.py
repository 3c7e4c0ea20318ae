"""One run of one cell: set up, warm up, measure, check, report.

  1. Set-up (counted in ``setup_s``): weights from the seed on the device,
     the engine as the configuration states it, every mixed-step width the
     cell can use and the page-copy program compiled (or loaded from the
     persistent cache), then traffic until it is in steady state.
  2. The window: ``seconds`` of traffic through ``eng.step()``.  With
     ``trace`` the profiler records its first ``TRACE_S`` seconds.
  3. ``memory_peak_bytes`` is read, the engine and its cache are freed, and
     a sample of the finished requests is scored against the plain
     reference (``harness/correct.py``).
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

from harness import correct as correct_mod
from harness import drive, latency, spec
from harness import trace as trace_mod
from harness import traffic as traffic_mod
from harness import weights

TRACE_S = 3.0          # seconds of the window the profiler records
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refusal(RuntimeError):
    """No result may be printed: no chip, too few chips, no program."""


def say(key, value) -> None:
    print(f"{key}: {value}", flush=True)


def shapes_of(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"], "d": d, "h": h,
            "hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "glu": cfg["hidden_act"] == "silu"}


def program_config(configs, cfg_file: dict):
    """The program's ModelConfig as the configuration file states it; a
    width that disagrees with the file is an error, not a run."""
    prog = cfg_file["program"]
    cfg = configs.get(prog["arch"]).replace(**prog.get("overrides", {}))
    s = shapes_of(cfg_file)
    want = {"num_layers": s["layers"], "d_model": s["d"],
            "num_heads": s["h"], "num_kv_heads": s["hkv"],
            "head_dim": s["hd"], "d_ff": s["ff"], "vocab_size": s["v"],
            "tie_embeddings": cfg_file["tie_word_embeddings"]}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise spec.SpecError(f"program config differs from "
                             f"{cfg_file['name']}.json: {bad}")
    return cfg


def warm_widths(eng) -> list[int]:
    """Compile every mixed-step width ``step()`` can pick, and the COW page
    copy, on span-0 inputs: span 0 leaves every row's cache untouched."""
    import jax
    import jax.numpy as jnp
    from repro.serving import engine as engine_mod

    b = eng.batch
    zeros = jnp.zeros((b,), jnp.int32)
    widths = list(engine_mod.mixed_width_buckets(eng.chunk_size))
    for w in widths:
        _, eng.cache = eng._mixed(eng.params, eng.cache,
                                  jnp.zeros((b, w), jnp.int32), zeros, zeros,
                                  jax.random.PRNGKey(0))
    if eng.paged:
        none = jnp.full((b,), -1, jnp.int32)
        eng.cache = eng._copy_pages(eng.cache, none, none)
    jax.block_until_ready(eng.cache)
    return widths


def prepare(cell, seed: int, *, root=spec.ROOT, require_chip: bool = True,
            fault=None) -> SimpleNamespace:
    """Import the program, check the chip, make the weights and the engine
    and compile every width: the set-up every run of the cell pays."""
    src = spec.Path(root) / "src"
    if not (src / "repro").is_dir():
        raise Refusal(f"the program is missing ({src / 'repro'})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    # One fixed cache directory inside the checkout; the program takes it.
    cache_dir = spec.Path(root) / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    import jax
    from repro import configs
    from repro.models import lm
    from repro.runtime import compile_cache
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # The directory is the checkout's own: no eviction, whose bookkeeping
    # files a crashed writer can leave half made.
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refusal(f"JAX finds no device: {e}") from None
    if require_chip:
        if devices[0].platform != "tpu":
            raise Refusal(f"no TPU: JAX sees {devices[0].platform!r}")
        if len(devices) < cell.chips:
            raise Refusal(f"the cell needs {cell.chips} chips, JAX sees "
                          f"{len(devices)}")
    dev = devices[0]
    peaks = cell.device(dev.device_kind) if require_chip else None

    cfg = program_config(configs, cell.config)
    e = cell.config["engine"]
    if e["kv_dtype"] != "bfloat16" or e["spec_decode"] != "off":
        raise spec.SpecError("the engine runs bf16 pools without "
                             "speculation; the configuration says otherwise")
    params = weights.make(
        jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg)), seed)
    eng = ContinuousBatchingEngine(
        cfg, params, batch=e["batch"], max_len=e["max_len"],
        paged=e["paged"], page_size=e["page_size"],
        num_pages=e.get("num_pages"),
        prefix_sharing=e["prefix_sharing"], temperature=e["temperature"],
        seed=seed & 0x7FFFFFFF)
    if fault is not None:
        fault(eng)
    widths = warm_widths(eng)
    return SimpleNamespace(jax=jax, devices=devices, peaks=peaks, cfg=cfg,
                           params=params, eng=eng, Request=Request,
                           widths=widths)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench: dict | None = None,
        chip_dir=spec.CHIP_DIR, root=spec.ROOT, require_chip: bool = True,
        fault=None):
    """Returns (result dict, [(name, value, limit)] compared)."""
    cell = spec.Cell(workload, bench, chip_dir, root)
    say("workload", f"{workload} seed={seed} seconds={seconds} "
        f"trace={int(trace)}")
    env = prepare(cell, seed, root=root, require_chip=require_chip,
                  fault=fault)
    jax, dev = env.jax, env.devices[0]
    traffic = traffic_mod.make(cell.traffic, seed, env.cfg.vocab_size,
                               seconds)
    drv = drive.Loop(env.eng, traffic, env.Request)
    drv.run_until(traffic.warm)

    compiles = {k: 0 for k in COMPILE_EVENTS}

    def on_event(name, _secs, **_kw):
        if name in compiles:
            compiles[name] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = drv.now()
    setup_s = drv.origin + t0 - t_start
    t1 = t0 + seconds
    drv.record_steps = True
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        trace_mod.start(log_dir)

        def annotate(name, step):
            if step is None:
                return jax.profiler.TraceAnnotation(name)
            return jax.profiler.StepTraceAnnotation(name, step_num=step)
        drv.annotate = annotate
        drv.run_until(lambda now: now >= min(t1, t0 + TRACE_S))
        drv.annotate = None
        trace_mod.stop()
        for st in drv.steps:
            st["traced"] = True
    drv.run_until(lambda now: now >= t1)
    jax.monitoring.unregister_event_duration_listener(on_event)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    lat = latency.summarize(drv.recs, t0, t1)
    late = [x for r, x in zip(drv.recs, drv.late) if t0 <= r.due < t1]
    say("widths_warmed", env.widths)
    say("steps_in_window", len(drv.steps))
    say("requests_due_in_window", lat["due"])
    say("first_tokens_in_window", lat["first_tokens"])
    say("waiting_at_close", lat["waiting_at_close"])
    say("inter_token_gaps", len(lat["itl_s"]))
    say("output_tok_s", lat["tokens"] / lat["window_s"])
    say("generator_late_p50_ms", 1e3 * latency.pct(late, 50) if late else 0)
    say("generator_late_max_ms", 1e3 * max(late) if late else 0)
    say("compiles_in_window", {k.rsplit("/", 1)[1]: v
                               for k, v in compiles.items()})
    say("engine_stats", {k: v for k, v in env.eng.stats.items()
                         if isinstance(v, int) and v})

    ctx = SimpleNamespace(
        setup_s=setup_s, latency=lat, window_steps=drv.steps,
        traced_steps=[s for s in drv.steps if s.get("traced")],
        trace=None, shapes=shapes_of(cell.config), device=env.peaks,
        kernel=cell.kernel, notes=[])
    if trace:
        ctx.trace = trace_mod.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        say("traced_steps", f"{len(ctx.traced_steps)} run, "
            f"{len(ctx.trace.steps)} in the trace")
    metrics = {}
    for m in cell.metrics(trace):
        v = cell.reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    for note in ctx.notes:
        say("note", note)

    # -- the check, once the program's state is freed ----------------------
    finished = [r for r in drv.recs
                if r.req.status == "completed" and r.done <= t1]
    drv.eng = env.eng = None
    gc.collect()
    compared, detail = correct_mod.check(cell, env.params, finished, seed)
    for k, v in detail.items():
        say(k, v)
    ok = all(v <= lim for _, v, lim in compared) and detail["requests"] > 0

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(env.devices), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": lat["due"],
              "failed": lat["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_mod.busy_s(ctx.trace)
        device["window_s"] = trace_mod.window_s(ctx.trace)
        result["breakdown"] = {"device_ops": trace_mod.top_ops(ctx.trace),
                               "idle_gaps": trace_mod.idle_gaps(ctx.trace)}
    result["check"] = {n: {"value": v, "limit": lim}
                       for n, v, lim in compared}
    return result, compared
