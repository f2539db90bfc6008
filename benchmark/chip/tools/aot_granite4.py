#!/usr/bin/env python3
"""`aot_qwen3next.py` for the configuration whose state rows are most of what a
slot holds (Granite 4.0-H: 77 MB a sequence over 36 Mamba-2 layers, served as
five stacked runs): that tool takes the tree of parameters from
``reference.layer_shapes`` a published layer and an untied head, and builds the
engine with its state rows allocated on the CPU (4.7 GB here), so it cannot
compile this configuration without an edit; this sibling stacks the shapes of
each run as the program holds them, leaves the head out, and
builds the engine with the big arrays as shapes only.  Otherwise the same:
compile the serve programs at the real size for a v5e that is not attached, as
the engine dispatches them, and read what memory each needs, which paged
attention its layers took, how many kernels it holds, and whether a whole pool
or a whole state leaf is copied anywhere in it (both must be 0).  With
``--reference`` also the check's own program (`refcheck.make_gap_fn`, float32
`highest`, `max_len` tokens).  Costs no chip time; says nothing about results
or speed.

    JAX_PLATFORMS=cpu python3 benchmark/chip/tools/aot_granite4.py \\
        [--config benchmark/chip/configs/granite4-h-micro-serve.json] \\
        [--programs burst,decode,prefill3072,ext3072,...] [--reference] \\
        [--hlo-dir DIR]
"""
import argparse
import importlib
import json
import os
import re
import sys
import time

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (ROOT, CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        CHIP, "configs", "granite4-h-micro-serve.json"))
    ap.add_argument("--programs", default="burst,decode")
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--hlo-dir")
    ns = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu.serving import GenerationEngine
    jax.config.update("jax_enable_compilation_cache", False)
    # the program steers by platform: compile what the chip would trace
    fa = importlib.import_module("incubator_mxnet_tpu.kernels.flash_attention")
    fa._platform_of = lambda x: "tpu"     # steers kernels/mamba2.py too

    with open(ns.config) as f:
        cfg = json.load(f)
    ref = importlib.import_module("reference." + cfg["reference"])
    prog = importlib.import_module("programs." + cfg["program"])
    dep = cfg["deployment"]
    dt = jnp.dtype(dep["param_dtype"])
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    tree = {
        "embed_tokens": (V, d), "norm": (d,),
        "layers": [dict(ref.layer_shapes(cfg, i))
                   for i in range(cfg["num_hidden_layers"])]}
    is_shape = lambda s: isinstance(s, tuple)                    # noqa: E731
    net = prog.build_net(cfg)
    # the layers as the program holds them: each run of Mamba layers one
    served = [{name: tuple(getattr(layer, name).shape)
               for name in layer._names} for layer in net.layers]
    net.adopt_arrays(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, dt), dict(tree, layers=served),
        is_leaf=is_shape))
    # the state rows are gigabytes: the engine gets their shapes, no zeros
    real_zeros = jnp.zeros

    def zeros(shape, dtype=None, **kw):
        if np.prod(shape, dtype=np.int64) > 1 << 24:
            return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
        return real_zeros(shape, dtype, **kw)
    jnp.zeros = zeros
    try:
        # the engine lives on the CPU with a pool of one slot; its programs are
        # traced for the chip over the pool the deployment states
        eng = GenerationEngine(
            net, name=dep["model_name"], max_slots=dep["max_slots"],
            max_len=dep["max_len"], prefill_buckets=dep["prefill_buckets"],
            paged=True, block_size=dep["block_size"],
            num_blocks=1 + -(-dep["max_len"] // dep["block_size"]),
            prefix_cache=dep["prefix_cache"], scan_steps=dep["scan_steps"],
            logprobs_topn=dep["logprobs_topn"],
            state_snapshot_tokens=dep["state_snapshot_tokens"],
            state_snapshot_rows=dep["state_snapshot_rows"])
    finally:
        jnp.zeros = real_zeros
    S = dep["max_slots"]
    N = dep.get("num_blocks") or 1 + S * eng.max_blocks_per_slot
    eng._pool_shape, eng._position_major = eng.layout.pool_shape(
        N, eng.block_size, topo.devices[0])

    def sds(x, shape=None):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype, sharding=one)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    cache = tuple(sds(c, eng._pool_shape) for c in eng._cache) \
        + tuple(sds(c) for c in eng._recur)
    every = dep["state_snapshot_tokens"]
    params, aux = eng._param_fn()
    tail = (tuple(sds(p) for p in params), tuple(sds(a) for a in aux))
    state = jax.tree.map(sds, eng._slot_state())
    programs = {"burst": (eng._decode_burst_jit, ()),
                "decode": (eng._decode_jit, ())}
    for b in dep["prefill_buckets"]:
        programs[f"prefill{b}"] = (eng._prefill_jit,
                                   (i32(1, b), i32(2 + b // every)))
        programs[f"ext{b}"] = (eng._prefill_ext_jit,
                               (i32(1, b), i32(4 + b // every)))
    weights = sum(int(p.size) * p.dtype.itemsize for p in sum(tail, ()))
    pool_bytes = 2 * eng._n_kv * cache[0].dtype.itemsize
    for n in eng._pool_shape:
        pool_bytes *= n
    print(f"max_slots {S}, num_blocks {N}: weights {weights / 1e9:.2f} GB, "
          f"pool {pool_bytes / 1e9:.2f} GB stored as {eng._pool_shape} "
          f"({'position-major' if eng._position_major else 'as stated'}); "
          f"state rows {eng.state_bytes / 1e9:.2f} GB in "
          f"{len(eng._recur)} arrays of {eng._null_row + 1} rows", flush=True)
    pool_text = "{}[{}]".format(
        {"bfloat16": "bf16", "float32": "f32"}[str(cache[0].dtype)],
        ",".join(str(n) for n in eng._pool_shape))
    copy = re.compile(r"= " + re.escape(pool_text) + r"\{[^}]*\} copy\(")
    state_copy = re.compile(
        r"= f32\[" + str(eng._null_row + 1) + r",[0-9,]*\]\{[^}]*\} copy\(")
    for name in ns.programs.split(","):
        jitted, operands = programs[name]
        t0 = time.time()
        try:
            compiled = jitted.trace(*((cache, state) + operands + tail)
                                    ).lower(lowering_platforms=("tpu",)
                                            ).compile()
        except Exception as e:      # the compiler's own refusal is the answer
            print(f"{name}: REFUSED after {time.time() - t0:.0f} s: "
                  + str(e).split("\n\n")[0][:600], flush=True)
            continue
        m, text = compiled.memory_analysis(), compiled.as_text()
        print(f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB; paged attention "
              f"{eng._paged_attention}; kernels "
              f"{text.count('custom_call_target=\"tpu_custom_call\"')}; "
              f"whole-pool copies {len(copy.findall(text))}; whole-state "
              f"copies {len(state_copy.findall(text))}", flush=True)
        if ns.hlo_dir:
            with open(os.path.join(ns.hlo_dir, name + ".hlo"), "w") as f:
                f.write(text)
    if ns.reference:
        import refcheck
        T = dep["max_len"]
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s, dt, sharding=one), tree,
            is_leaf=is_shape)
        for precision in ["float32", cfg["check"]["control_precision"]]:
            t0 = time.time()
            try:
                m = refcheck.make_gap_fn(ref, cfg, precision).trace(
                    shapes, i32(1, T), i32(), i32()).lower(
                    lowering_platforms=("tpu",)).compile().memory_analysis()
            except Exception as e:
                print(f"reference {precision}: REFUSED after "
                      f"{time.time() - t0:.0f} s: "
                      + str(e).split("\n\n")[0][:600], flush=True)
                continue
            print(f"reference {precision} over {T} tokens: compiled in "
                  f"{time.time() - t0:.0f} s; arguments "
                  f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
                  f"{m.temp_size_in_bytes / 1e9:.2f} GB", flush=True)


if __name__ == "__main__":
    main()
