"""sha256 of the jaxpr of the serve programs of the five existing serve
configurations' tiny presets (run from the root of a checkout)."""
import hashlib, importlib, json, os, sys
ROOT = os.getcwd()
sys.path.insert(0, ROOT); sys.path.insert(0, os.path.join(ROOT, "benchmark", "chip"))
import jax, jax.numpy as jnp
from incubator_mxnet_tpu.serving import GenerationEngine
out = {}
for tiny in ("tiny_gpt", "tiny_afmoe", "tiny_smallthinker", "tiny_qwen3next", "tiny_dots3"):
    cfg = json.load(open(os.path.join(ROOT, "benchmark/chip/tests", tiny + ".json")))
    ref = importlib.import_module("reference." + cfg["reference"])
    prog = importlib.import_module("programs." + cfg["program"])
    net = prog.build_net(cfg)
    prog.load_weights(net, ref.init_params(cfg, 1))
    dep = cfg["deployment"]
    kw = {k: dep[k] for k in ("state_snapshot_tokens", "state_snapshot_rows") if k in dep}
    eng = GenerationEngine(net, name=tiny, max_slots=dep["max_slots"], max_len=dep["max_len"],
                           prefill_buckets=dep["prefill_buckets"], block_size=dep["block_size"],
                           num_blocks=dep.get("num_blocks"), prefix_cache=dep["prefix_cache"],
                           scan_steps=dep["scan_steps"], logprobs_topn=dep["logprobs_topn"], **kw)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    cache = tuple(map(sds, eng._cache + eng._recur))
    state = jax.tree.map(sds, eng._slot_state())
    params, aux = eng._param_fn()
    tail = (tuple(map(sds, params)), tuple(map(sds, aux)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    b = eng.prefill_buckets[0]
    every = eng.state_snapshot_tokens
    extra = (b // every) if every else 0
    progs = {"decode": (eng._decode_jit, ()), "burst": (eng._decode_burst_jit, ()),
             "prefill": (eng._prefill_jit, (i32(1, b), i32(2 + extra))),
             "ext": (eng._prefill_ext_jit, (i32(1, b), i32((4 if eng._state_layers else 3) + extra)))}
    for name, (jitted, ops) in progs.items():
        text = str(jitted.trace(cache, state, *ops, *tail).jaxpr)
        out[f"{tiny}:{name}"] = hashlib.sha256(text.encode()).hexdigest()[:16]
print(json.dumps(out, indent=0))
