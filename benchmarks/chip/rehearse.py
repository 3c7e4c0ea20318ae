#!/usr/bin/env python3
"""Compile a cell's mixed-step widths at the cell's sizes for a described
TPU v5e, with no chip attached, and print each program's memory analysis.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py <workload> [...]

Nothing runs and nothing is allocated (shapes only), so this says nothing
about time or results; it catches what the chip's compiler would refuse
(tiling, VMEM, a program that does not fit) before chip time is spent.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))


def main(workloads) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import cell as cell_mod, spec
    from repro import configs
    from repro.kernels import ops
    from repro.models import lm
    from repro.serving import engine as engine_mod

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    ops._on_tpu = lambda: True        # resolve the kernels as the chip would

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    for w in workloads:
        c = spec.Cell(w)
        cfg = cell_mod.program_config(configs, c.config)
        e = c.config["engine"]
        b, maxp = e["batch"], -(-e["max_len"] // e["page_size"])
        params = shaped(lm.abstract_params(cfg))
        cache = shaped(jax.eval_shape(lambda: lm.init_cache(
            cfg, b, e["max_len"], paged=True, page_size=e["page_size"],
            num_pages=e.get("num_pages", b * maxp) + 1)))
        nbytes = lambda t: sum(x.size * x.dtype.itemsize
                               for x in jax.tree.leaves(t))
        print(f"{w}: params {nbytes(params)} B, cache {nbytes(cache)} B",
              flush=True)
        vec = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        step = jax.jit(engine_mod.make_mixed_step_fn(cfg),
                       donate_argnums=(1,))
        for width in engine_mod.mixed_width_buckets(32):
            toks = jax.ShapeDtypeStruct((b, width), jnp.int32, sharding=one)
            try:
                compiled = step.lower(params, cache, toks, vec, vec,
                                      key).compile()
            except Exception as e:        # report and go on to the next
                print(f"  width {width}: {str(e).splitlines()[0]}")
                continue
            m = compiled.memory_analysis()
            assert "tpu_custom_call" in compiled.as_text()
            print(f"  width {width}: argument {m.argument_size_in_bytes} "
                  f"output {m.output_size_in_bytes} alias "
                  f"{m.alias_size_in_bytes} temp {m.temp_size_in_bytes}",
                  flush=True)
        pages = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
        m = jax.jit(lm.copy_pages, donate_argnums=(0,)).lower(
            cache, pages, pages).compile().memory_analysis()
        print(f"  copy_pages: temp {m.temp_size_in_bytes}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
