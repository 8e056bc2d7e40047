"""The collectives of a training cell's step, compiled for a v5e 2x2 from the
CPU sandbox: what the partitioner makes the chips move, read before any chip
time is spent (docs/PERFORMANCE.md "Collective overlap").

    python3 scripts/step_collectives.py fsdp4

The argument names a traffic file of kind `train` (`benchmarks/traffic/`);
the configuration is the one its cell names in `BENCHMARK.json`. Prints the
compiler's temporaries a chip and one line a distinct collective: the
`while` loop it runs in (the layer scan's bodies are `.../jvp(Llama)/while`
and `.../transpose(jvp(Llama))/while`), its kind, the shape a chip receives,
the bytes, how often, and the source op it was put in for. Counts and bytes
only: nothing here says how long anything takes.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    from jax.experimental import topologies

    from ray_lightning_tpu.analysis.collectives import (
        format_collectives,
        step_collectives,
    )
    from ray_lightning_tpu.ops import dispatch
    from tests.test_tpu_aot_compile import cell_step_compiled

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    dispatch.on_tpu = lambda: True      # kernels lower through Mosaic
    v5e = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)
    cell, compiled = cell_step_compiled(argv[0], v5e)

    mem = compiled.memory_analysis()
    print(f"{cell['name']}: temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB a chip, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB")
    cols = step_collectives(compiled.as_text())
    print(format_collectives(cols))
    print(f"{len(cols)} collectives, "
          f"{sum(c.nbytes for c in cols if c.loop) / 1e6:.0f} MB received "
          "a trip of the loops, "
          f"{sum(c.nbytes for c in cols if not c.loop) / 1e6:.0f} MB outside")


if __name__ == "__main__":
    main(sys.argv[1:])
