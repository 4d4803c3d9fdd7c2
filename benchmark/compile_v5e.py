"""Compile each cell's largest warm shapes for a TPU v5e that is
described, not attached (the on-chip-measurement guide, section 2):
what the chip's compiler refuses (layout, memory) fails here, at no
chip time.  Nothing runs, so this says nothing about speed.  Not a test:
it loads libtpu, which one process at a time may hold.

    JAX_PLATFORMS=cpu python3 benchmark/compile_v5e.py
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

HBM_BYTES = 16 * 1024 ** 3
TOP_L = 4096  # the largest line bucket the weblog corpus reaches (lines to 3.3 KB)


def shapes(config, traffic):
    if config["driver"] == "etl_job":
        return [(config["batch_lines"], TOP_L)]
    return [(traffic["arrivals"]["max_lines"], TOP_L), (64, 128)]


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    from logparser_tpu.tpu.batch import TpuBatchParser

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {c["name"]: c for c in bench["configs"]}
    done = set()
    for cell in bench["workloads"]:
        with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
            config = json.load(f)
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        parser = TpuBatchParser(config["log_format"], config["fields"],
                                view_fields=())
        agg = traffic.get("query", {}).get("aggregate")
        for b, l in shapes(config, traffic):
            key = (cell["config"], bool(agg), b, l)
            if key in done:
                continue
            done.add(key)
            args = (jax.ShapeDtypeStruct((b, l), jnp.uint8, sharding=one),
                    jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one))
            t0 = time.monotonic()
            if agg:
                fn = parser._agg_executor(parser._resolve_agg_spec(agg))
                compiled = fn.lower(
                    *args, jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
                    jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)).compile()
            else:
                compiled = parser.device_fn()._jit.lower(*args).compile()
            mem = compiled.memory_analysis()
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes)
            print(json.dumps({"cell": cell["name"], "shape": [b, l],
                              "aggregate": bool(agg),
                              "compile_s": round(time.monotonic() - t0, 1),
                              "device_bytes": total,
                              "fits": 0 < total < HBM_BYTES}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
