#!/usr/bin/env python3
"""Rehearsal 2 of the README for an AFMoE configuration (`aot_memory.py` builds
GPT-2's net and pool): compile its serve programs at the real size for a v5e
that is not attached, and read what memory each needs.  The net's parameters
are shapes only — 8 GB of weights are never made.  Costs no chip time; says
nothing about results or speed.

    JAX_PLATFORMS=cpu python3 benchmark/chip/tools/aot_afmoe.py \\
        --config benchmark/chip/configs/trinity-large-serve-ep8.json \\
        [--programs burst,decode,prefill512,ext512,...] [--hlo-dir DIR]
"""
import argparse
import importlib
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
    ap.add_argument("--programs", default="burst,decode")
    ap.add_argument("--hlo-dir")
    ns = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu import random as mx_random
    from incubator_mxnet_tpu.serving import GenerationEngine
    from programs import afmoe_serve
    from reference import afmoe as ref
    jax.config.update("jax_enable_compilation_cache", False)
    # the program steers by platform: compile what the chip would trace
    fa = importlib.import_module("incubator_mxnet_tpu.kernels.flash_attention")
    fa._platform_of = lambda x: "tpu"

    with open(ns.config) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]
    dt = ref.param_dtype(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shape = lambda s: jax.ShapeDtypeStruct(s, dt)               # noqa: E731
    net = afmoe_serve.build_net(cfg)
    net.adopt_arrays({
        "embed_tokens": shape((V, d)), "norm": shape((d,)),
        "lm_head": shape((d, V)),
        "layers": [{n: shape(s) for n, s in ref.layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"])]})
    eng = GenerationEngine(
        net, name=dep["model_name"], max_slots=dep["max_slots"],
        max_len=dep["max_len"], prefill_buckets=dep["prefill_buckets"],
        paged=True, block_size=dep["block_size"],
        num_blocks=1 + -(-dep["max_len"] // dep["block_size"]),
        prefix_cache=dep["prefix_cache"], scan_steps=dep["scan_steps"],
        logprobs_topn=dep["logprobs_topn"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x, shape=None):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype, sharding=one)

    S = dep["max_slots"]
    N = dep.get("num_blocks") or 1 + S * eng.max_blocks_per_slot
    H, bs, D = eng.num_heads, eng.block_size, eng.head_dim
    cache = tuple(sds(c, (N, H, bs, D)) for c in eng._cache)
    params, aux = eng._param_fn()
    pv, av = tuple(sds(p) for p in params), tuple(sds(a) for a in aux)
    key = sds(mx_random.new_key(eng._ctx))
    samp = tuple(sds(a) for a in eng._samp_tuple())
    slot_samp = tuple(sds(a) for a in eng._slot_samp(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    tables, row = i32(S, eng.max_blocks_per_slot), i32(eng.max_blocks_per_slot)
    programs = {
        "burst": (eng._decode_burst_paged_pure,
                  (cache, i32(S, 1), i32(S), i32(S), i32(S),
                   jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one),
                   tables, samp, pv, av, key)),
        "decode": (eng._decode_paged_pure,
                   (cache, i32(S, 1), i32(S), tables, samp, pv, av, key)),
    }
    for b in dep["prefill_buckets"]:
        programs[f"prefill{b}"] = (
            eng._prefill_paged_pure,
            (cache, i32(1, b), i32(), row, slot_samp, pv, av, key))
        programs[f"ext{b}"] = (
            eng._prefill_ext_pure,
            (cache, i32(1, b), i32(), i32(), row, slot_samp, pv, av, key))
    weights = sum(int(p.size) * p.dtype.itemsize for p in pv + av)
    print(f"max_slots {S}, num_blocks {N}: weights {weights / 1e9:.2f} GB, "
          f"pool {eng.layout.block_bytes(bs) * N / 1e9:.2f} GB", flush=True)
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
                  f"{m.temp_size_in_bytes / 1e9:.2f} GB; paged attention "
                  f"{eng._paged_attention}", flush=True)
            if ns.hlo_dir:
                with open(os.path.join(ns.hlo_dir, name + ".hlo"), "w") as f:
                    f.write(compiled.as_text())
        except Exception as e:      # the compiler's own refusal is the answer
            print(f"{name}: REFUSED after {time.time() - t0:.0f} s: "
                  + str(e).split("\n\n")[0][:600], flush=True)


if __name__ == "__main__":
    main()
