#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e,
without a chip, and print their memory analysis.

    JAX_PLATFORMS=cpu python3 bench/aot.py <config> [<config> ...]

For each configuration file under ``bench/configs`` it builds the engine's
program set at the file's slots and context, and compiles the pure-decode
tick, the padded chunk tick (all slots, the largest chunk) and a packed
tick at the top kv bucket, with the Pallas kernels. It prints the bytes of
the parameters and the cache, and per program the argument, output,
temporary and aliased bytes the compiler reports.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_XLA_CACHE"] = "0"
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.kernels import ops as kops
    from repro.serving import engine as engine_mod

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    kops.set_backend("tpu")

    def spec(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    for name in names:
        config = harness.load_json(f"bench/configs/{name}.json")
        cfg = harness.program_config(config)
        S, L = config["serving"]["max_slots"], config["serving"]["max_len"]
        js = engine_mod._EngineJits(cfg, 0.0)
        params = spec(jax.eval_shape(
            lambda k: js.model.init_params(k)[0], jax.random.key(0)))
        cache = spec(jax.eval_shape(lambda: js.model.init_cache(S, L)[0]))
        pb = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
        cb = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        print(f"{name}: params {pb} bytes, cache {cb} bytes "
              f"({S} slots x {L})", flush=True)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                              sharding=one)
        progs = {
            "mixed_decode": lambda: js.mixed_decode.lower(
                params, i32(S), cache,
                jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one), kv=L),
            "prefill_chunk": lambda: js.prefill_chunk.lower(
                params, i32(S, 256), cache, i32(S), i32(S), kv=L),
            "prefill_packed": lambda: js.prefill_packed.lower(
                params, i32(1024), cache, i32(S), i32(S), i32(S),
                kv=L, chunk=256),
        }
        for pname, lower in progs.items():
            c = lower().compile()
            m = c.memory_analysis()
            print(f"  {pname}: arguments {m.argument_size_in_bytes} output "
                  f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
                  f"aliased {m.alias_size_in_bytes} kernel "
                  f"{'tpu_custom_call' in c.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["yi-6b", "granite-3.0-8b-l20"]))
