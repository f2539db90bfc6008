#!/usr/bin/env python3
"""`aot_smallthinker.py` for a configuration whose layers keep ROWS of their
own (a latent cache: `KVLayout.rows`): that tool hands the programs 2 x layers
pools of one shape, and this configuration has one pool a row a layer in three
widths.  Otherwise the same: compile the serve programs at the real size for
a v5e that is not attached, as the engine dispatches them — each pool in the
shape `KVLayout.row_pool_shape` gives on that chip, the per-slot operands the
engine's slot state — and read what memory each needs, which paged attention
its layers took, how many kernels it holds and whether a whole pool is copied
anywhere in it (must be 0).  With ``--reference`` also the check's own program
(`refcheck.make_gap_fn`, float32 `highest`, `max_len` tokens).  The net's
parameters are shapes only: 7.3 GB of weights are never made.  Costs no chip
time; says nothing about results or speed.

    JAX_PLATFORMS=cpu python3 benchmark/chip/tools/aot_dots3.py \\
        [--config benchmark/chip/configs/dots3-note-serve-tp8-l5.json] \\
        [--programs burst,decode,prefill1024,ext1024,prefill4096,ext4096] \\
        [--num-blocks N] [--reference] [--hlo-dir DIR]
"""
import argparse
import importlib
import json
import os
import re
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
    ap.add_argument("--config", default=os.path.join(
        CHIP, "configs", "dots3-note-serve-tp8-l5.json"))
    ap.add_argument("--programs", default="burst,decode")
    ap.add_argument("--num-blocks", type=int)
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
    fa._platform_of = lambda x: "tpu"     # every kernel file reads this one

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
        "embed_tokens": (V, d), "norm": (d,), "lm_head": (d, V),
        "layers": [dict(ref.layer_shapes(cfg, i))
                   for i in range(cfg["num_hidden_layers"])]}
    is_shape = lambda s: isinstance(s, tuple)                    # noqa: E731
    net = prog.build_net(cfg)
    net.adopt_arrays(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, dt), tree, is_leaf=is_shape))
    # the engine lives on the CPU with a pool of one slot; its programs are
    # traced for the chip over the pools the deployment states
    eng = GenerationEngine(
        net, name=dep["model_name"], max_slots=dep["max_slots"],
        max_len=dep["max_len"], prefill_buckets=dep["prefill_buckets"],
        paged=True, block_size=dep["block_size"],
        num_blocks=1 + -(-dep["max_len"] // dep["block_size"]),
        prefix_cache=dep["prefix_cache"], scan_steps=dep["scan_steps"],
        logprobs_topn=dep["logprobs_topn"])
    S = dep["max_slots"]
    N = ns.num_blocks or dep.get("num_blocks") \
        or 1 + S * eng.max_blocks_per_slot

    def sds(x, shape=None):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype, sharding=one)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    cache = [None] * len(eng._cache)
    for l, ids in eng._pool_ids.items():
        for i, (features, _) in zip(ids, eng.layout.layer_rows(l)):
            cache[i] = sds(eng._cache[i], eng.layout.row_pool_shape(
                N, eng.block_size, features, topo.devices[0]))
    cache = tuple(cache)
    params, aux = eng._param_fn()
    tail = (tuple(sds(p) for p in params), tuple(sds(a) for a in aux))
    state = jax.tree.map(sds, eng._slot_state())
    programs = {"burst": (eng._decode_burst_jit, ()),
                "decode": (eng._decode_jit, ())}
    for b in dep["prefill_buckets"]:
        programs[f"prefill{b}"] = (eng._prefill_jit, (i32(1, b), i32(2)))
        programs[f"ext{b}"] = (eng._prefill_ext_jit, (i32(1, b), i32(3)))
    weights = sum(int(p.size) * p.dtype.itemsize for p in sum(tail, ()))
    pool_bytes = 0
    for c in cache:
        n = c.dtype.itemsize
        for k in c.shape:
            n *= k
        pool_bytes += n
    shapes = sorted({c.shape for c in cache})
    print(f"max_slots {S}, num_blocks {N}: weights {weights / 1e9:.2f} GB, "
          f"pools {pool_bytes / 1e9:.2f} GB in {len(cache)} arrays stored as "
          f"{shapes} ({pool_bytes / N / eng.block_size:.0f} B a position "
          f"stored, {eng.layout.block_bytes(1)} B stated)", flush=True)
    kind = {"bfloat16": "bf16", "float32": "f32"}[str(cache[0].dtype)]
    copy = re.compile("|".join(
        r"= " + re.escape("{}[{}]".format(kind, ",".join(map(str, s))))
        + r"\{[^}]*\} copy\(" for s in shapes))
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
              f"whole-pool copies {len(copy.findall(text))}", flush=True)
        if ns.hlo_dir:
            os.makedirs(ns.hlo_dir, exist_ok=True)
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
