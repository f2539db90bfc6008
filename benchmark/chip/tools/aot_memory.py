#!/usr/bin/env python3
"""Rehearsal 2 of the README: compile a serve configuration's programs at the
real size for a v5e that is not attached, and read what memory each needs.
Costs no chip time; says nothing about results or speed.

    JAX_PLATFORMS=cpu python3 benchmark/chip/tools/aot_memory.py \\
        --config benchmark/chip/configs/gpt2-medium-serve.json \\
        [--max-slots N --num-blocks N] [--programs burst,decode,prefill]
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (ROOT, CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--max-slots", type=int)
    ap.add_argument("--num-blocks", type=int)
    ap.add_argument("--programs", default="burst,decode,prefill")
    ns = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu import random as mx_random
    from incubator_mxnet_tpu.serving import GenerationEngine
    from programs import gpt_serve

    with open(ns.config) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]
    if ns.max_slots:
        dep["max_slots"] = ns.max_slots
    if ns.num_blocks:
        dep["num_blocks"] = ns.num_blocks
    net = gpt_serve.build_net(cfg)
    eng = GenerationEngine(
        net, name=dep["model_name"], max_slots=dep["max_slots"],
        max_len=dep["max_len"], prefill_buckets=dep["prefill_buckets"],
        paged=True, block_size=dep["block_size"], num_blocks=65,
        prefix_cache=dep["prefix_cache"], scan_steps=dep["scan_steps"],
        logprobs_topn=dep["logprobs_topn"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x, shape=None):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype, sharding=one)

    S, N = dep["max_slots"], dep["num_blocks"]
    H, bs, D = eng.num_heads, eng.block_size, eng.head_dim
    cache = tuple(sds(c, (N, H, bs, D)) for c in eng._cache)
    params, aux = eng._param_fn()
    pv, av = tuple(sds(p) for p in params), tuple(sds(a) for a in aux)
    key = sds(mx_random.new_key(eng._ctx))
    samp = tuple(sds(a) for a in eng._samp_tuple())
    slot_samp = tuple(sds(a) for a in eng._slot_samp(0))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    tables = i32(S, eng.max_blocks_per_slot)
    programs = {
        "burst": (eng._decode_burst_paged_pure,
                  (cache, i32(S, 1), i32(S), i32(S), i32(S),
                   jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one),
                   tables, samp, pv, av, key)),
        "decode": (eng._decode_paged_pure,
                   (cache, i32(S, 1), i32(S), tables, samp, pv, av, key)),
        "prefill": (eng._prefill_paged_pure,
                    (cache, i32(1, max(dep["prefill_buckets"])), i32(),
                     i32(eng.max_blocks_per_slot), slot_samp, pv, av, key)),
    }
    print(f"max_slots {S}, num_blocks {N}: pool "
          f"{2 * eng.num_layers * N * H * bs * D * 4 / 1e9:.2f} GB", flush=True)
    for name in ns.programs.split(","):
        fn, args = programs[name]
        t0 = time.time()
        try:
            compiled = jax.jit(fn, donate_argnums=(0,)).trace(*args).lower(
                lowering_platforms=("tpu",)).compile()
            m = compiled.memory_analysis()
            print(f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
                  f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
                  f"{m.output_size_in_bytes / 1e9:.2f} GB, aliased "
                  f"{m.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
                  f"{m.temp_size_in_bytes / 1e9:.2f} GB", flush=True)
        except Exception as e:      # the compiler's own refusal is the answer
            print(f"{name}: REFUSED after {time.time() - t0:.0f} s: "
                  + str(e).split("\n\n")[0][:600], flush=True)


if __name__ == "__main__":
    main()
