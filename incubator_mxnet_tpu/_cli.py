"""Console entry points (reference analog: the reference ships its CLI
as ``tools/*.py`` scripts; packaging exposes them as ``im2rec`` and
``mxtpu-launch`` commands).

In a source checkout the implementations live in ``tools/`` next to the
package; when only the wheel is installed the source scripts are absent
and we fail with a clear message rather than a stack trace.
"""
from __future__ import annotations

import importlib.util
import os
import sys
import time


def _load_tool(name):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(here, "tools", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(
            f"{name}: the '{name}' tool ships in the source tree "
            f"(tools/{name}.py) — run from a checkout of the repository")
    spec = importlib.util.spec_from_file_location(f"_tool_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def im2rec_main():
    """Pack an image list into RecordIO (tools/im2rec.py)."""
    sys.exit(_load_tool("im2rec").main())


def launch_main():
    """Spawn a multi-process training job (tools/launch.py)."""
    sys.exit(_load_tool("launch").main())


def stats_main():
    """``mxtpu-stats`` — run a script under runtime telemetry and print
    the metrics afterwards::

        mxtpu-stats [--format prometheus|json] [--out PATH]
                    [--serve [--port N]] [--slo] [--flight-dump PATH]
                    script.py [args...]
        mxtpu-stats --fleet http://router:9000 [--slo] [--out PATH]
        mxtpu-stats --fleet URL --memory | --programs | --health |
                    --profile SECS

    With ``--fleet`` no script runs: the federated fleet view is pulled
    from a running ``mxtpu-router`` (or a single replica) instead — its
    aggregated ``/metrics`` exposition, merged ``/slo`` with ``--slo``,
    the device-memory breakdown with ``--memory``, the runtime
    program-set inventory with ``--programs``, the health-plane report
    with ``--health``, or an on-demand profiler
    capture (``POST /debug/profile``, fanned out to every replica when
    URL is a router) with ``--profile SECONDS`` — printed to stdout or
    ``--out``.

    Otherwise the script runs in-process (as ``__main__``) with the telemetry
    collector started, so every layer (op dispatch, compile cache,
    kvstore, trainer, dataloader) is observed without touching the
    script.  Metrics go to --out (or stdout) when the script finishes —
    including when it raises.  With ``--serve`` the live HTTP exporter
    runs for the duration of the script (``/metrics``, ``/healthz``,
    ``/trace`` on --port, default 9100), so a long training run can be
    scraped and its span tree inspected while it executes."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="mxtpu-stats",
        description="run a python script with MXNET_TELEMETRY collection "
                    "and print the metrics dump")
    ap.add_argument("--format", choices=("prometheus", "json"),
                    default="prometheus")
    ap.add_argument("--out", default=None,
                    help="write the dump here instead of stdout")
    ap.add_argument("--serve", action="store_true",
                    help="serve live /metrics, /healthz and /trace over "
                         "HTTP while the script runs")
    ap.add_argument("--port", type=int, default=9100,
                    help="HTTP exporter port for --serve (default 9100; "
                         "0 picks an ephemeral port)")
    ap.add_argument("--slo", action="store_true",
                    help="also print the per-model SLO state (burn "
                         "rate, error budget) after the script")
    ap.add_argument("--flight-dump", metavar="PATH", default=None,
                    help="write a flight-recorder postmortem JSON to "
                         "PATH after the script (always written, even "
                         "on success — useful for inspecting the ring)")
    ap.add_argument("--fleet", metavar="URL", default=None,
                    help="pull the federated fleet view from a running "
                         "mxtpu-router at URL instead of running a "
                         "script (aggregated /metrics, or merged /slo "
                         "with --slo)")
    ap.add_argument("--memory", action="store_true",
                    help="with --fleet: fetch the device-memory "
                         "breakdown (GET /memory — per-owner HBM "
                         "attribution) instead of /metrics")
    ap.add_argument("--programs", action="store_true",
                    help="with --fleet: fetch the runtime program-set "
                         "inventory (GET /programs — dispatch ledger + "
                         "expected-vs-compiled accounting)")
    ap.add_argument("--health", action="store_true",
                    help="with --fleet: fetch the health-plane report "
                         "(GET /health — anomaly state, StepHealth ring "
                         "tail, per-model decode stats; worst-replica "
                         "rollup when URL is a router)")
    ap.add_argument("--profile", metavar="SECONDS", type=float,
                    default=None,
                    help="with --fleet: trigger an on-demand profiler "
                         "capture (POST /debug/profile?seconds=) and "
                         "print the per-replica artifact paths")
    ap.add_argument("script", nargs="?", default=None,
                    help="python script to run")
    ap.add_argument("args", nargs=argparse.REMAINDER,
                    help="arguments passed to the script")
    ns = ap.parse_args()

    if ns.fleet:
        sys.exit(_fleet_stats(ns))
    if ns.memory or ns.programs or ns.health or ns.profile is not None:
        ap.error("--memory/--programs/--health/--profile need --fleet "
                 "URL (they query a running server)")
    if ns.script is None:
        ap.error("a script is required unless --fleet URL is given")

    from . import telemetry
    telemetry.start()
    if ns.serve:
        from . import telemetry_http
        srv = telemetry_http.start_server(ns.port)
        sys.stderr.write(
            f"mxtpu-stats: serving /metrics /healthz /trace on "
            f"http://0.0.0.0:{srv.server_address[1]}\n")

    import runpy
    sys.argv = [ns.script] + ns.args
    status = 0
    try:
        runpy.run_path(ns.script, run_name="__main__")
    except SystemExit as e:
        status = e.code if isinstance(e.code, int) else (0 if e.code is None
                                                         else 1)
    except BaseException:
        import traceback
        traceback.print_exc()
        status = 1

    if ns.format == "prometheus":
        text = telemetry.render_prometheus()
    else:
        import json
        text = json.dumps(telemetry.snapshot(), indent=2, default=str) + "\n"
    if ns.out:
        with open(ns.out, "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text)
    if ns.slo:
        import json
        from . import telemetry_http
        sys.stdout.write(json.dumps(telemetry_http.slo_body(), indent=2,
                                    default=str) + "\n")
    if ns.flight_dump:
        from . import telemetry_ring
        path = telemetry_ring.recorder.dump("cli", path=ns.flight_dump)
        sys.stderr.write(f"mxtpu-stats: flight dump -> {path}\n")
    sys.exit(status)


def _fleet_stats(ns) -> int:
    """``mxtpu-stats --fleet URL``: fetch the router's federated view
    (``/metrics`` by default; ``--slo``/``--memory``/``--programs``/
    ``--health`` pick the JSON views, ``--profile SECONDS`` triggers a
    capture)."""
    from urllib.error import URLError
    from urllib.request import Request, urlopen

    base = ns.fleet.rstrip("/")
    if "://" not in base:
        base = "http://" + base
    timeout = 10.0
    req = None
    if ns.profile is not None:
        # the capture blocks server-side for the window plus profiler
        # startup and trace serialization; wait them out
        path = f"/debug/profile?seconds={ns.profile}"
        timeout = float(ns.profile) + max(30.0, 2.0 * float(ns.profile))
        req = Request(base + path, data=b"{}", method="POST",
                      headers={"Content-Type": "application/json"})
    elif ns.memory:
        path = "/memory"
    elif ns.programs:
        path = "/programs"
    elif ns.health:
        path = "/health"
    elif ns.slo:
        path = "/slo"
    else:
        path = "/metrics"
    try:
        with urlopen(req or (base + path), timeout=timeout) as resp:
            text = resp.read().decode("utf-8", "replace")
    except (URLError, OSError) as e:
        sys.stderr.write(f"mxtpu-stats: --fleet {base}{path}: {e}\n")
        return 1
    if not text.endswith("\n"):
        text += "\n"
    if ns.out:
        with open(ns.out, "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text)
    return 0


def _load_generation_engine(name, cfg_path, max_slots=None, max_len=None,
                            block_size=None, scan_steps=None):
    """Build a :class:`serving.GenerationEngine` from a ``--gen-model``
    JSON config: architecture kwargs for ``models.gpt.GPTModel`` plus a
    ``"params"`` weights file (``Block.save_parameters`` format,
    resolved relative to the config) and optional ``"max_slots"`` /
    ``"max_len"`` engine knobs.  Omitting ``"params"`` serves random
    weights — useful for smoke tests and load drills."""
    import json

    import numpy as np

    from . import initializer as init
    from . import ndarray as nd
    from .models.gpt import GPTModel
    from .serving import GenerationEngine

    with open(cfg_path) as f:
        cfg = dict(json.load(f))
    if "vocab_size" not in cfg:
        raise SystemExit(
            f"mxtpu-serve: {cfg_path}: generation config needs at "
            'least {"vocab_size": N}')
    params = cfg.pop("params", None)
    cfg_slots = cfg.pop("max_slots", None)
    cfg_len = cfg.pop("max_len", None)
    cfg_bs = cfg.pop("block_size", None)
    cfg_spec_k = cfg.pop("spec_k", None)    # draft configs only
    cfg_scan = cfg.pop("scan_steps", None)
    cfg_lp = cfg.pop("logprobs_topn", None)
    max_slots = cfg_slots if max_slots is None else max_slots
    max_len = cfg_len if max_len is None else max_len
    block_size = cfg_bs if block_size is None else block_size
    scan_steps = cfg_scan if scan_steps is None else scan_steps
    cfg.setdefault("dropout", 0.0)      # serving never trains
    net = GPTModel(**cfg)
    net.initialize(init.Normal(0.02))
    net(nd.array(np.zeros((1, 2), np.int32)))   # settle deferred shapes
    if params is not None:
        if not os.path.isabs(params):
            params = os.path.join(os.path.dirname(
                os.path.abspath(cfg_path)), params)
        net.load_parameters(params)
    engine = GenerationEngine(net, name=name, max_slots=max_slots,
                              max_len=max_len, block_size=block_size,
                              scan_steps=scan_steps,
                              logprobs_topn=cfg_lp)
    # surfaced by serve_main when this config backs a --gen-draft
    engine._cfg_spec_k = cfg_spec_k
    return engine


def serve_main():
    """``mxtpu-serve`` — dynamic-batching inference server over exported
    model artifacts (see docs/serving.md)::

        mxtpu-serve --model mnist=/models/mnist:7 \\
                    --model small=/models/small \\
                    [--gen-model gpt=/models/gpt.json] \\
                    [--gen-draft gpt=/models/gpt-small.json] \\
                    [--port N] [--max-batch N] [--max-delay-ms F]
                    [--queue N] [--input-names data]
                    [--input-specs 784] [--warmup] [--preload]
                    [--gen-slots N] [--gen-max-len N]
                    [--gen-block-size N]

    Each ``--model`` is ``NAME=PREFIX[:EPOCH]`` naming a
    ``HybridBlock.export`` / ``model.save_checkpoint`` pair
    (``PREFIX-symbol.json`` + ``PREFIX-EPOCH.params``).  Serves
    ``/v1/models/<name>:predict``, the model registry, ``/healthz``,
    ``/readyz`` and ``/metrics`` until SIGTERM/Ctrl-C, then drains:
    ``/readyz`` flips to 503, in-flight requests finish (within
    ``MXNET_DRAIN_SECONDS``), and the port closes cleanly — no reset
    connections.  Knobs default from ``MXNET_SERVE_*``
    (docs/env_var.md).

    Each ``--gen-model`` is ``NAME=CONFIG.json`` describing a GPT-style
    generation model: the JSON carries the architecture kwargs
    (``vocab_size``, ``units``, ``num_layers``, ...) plus ``"params"``
    — a ``Block.save_parameters`` weights file, resolved relative to
    the config — and optional ``"max_slots"``/``"max_len"`` engine
    knobs.  Generation models serve token streams at
    ``/v1/models/<NAME>:generate`` behind continuous batching
    (docs/serving.md); ``--gen-slots`` / ``--gen-max-len`` override the
    config and the ``MXNET_GEN_MAX_SLOTS`` / ``MXNET_GEN_MAX_LEN``
    env defaults.  The KV cache is a block pool with prefix sharing;
    ``--gen-block-size`` sets tokens per block
    (``MXNET_KV_BLOCK_SIZE``).

    ``--gen-draft NAME=CONFIG.json`` attaches a small draft model to
    the generation model registered as ``NAME``, enabling speculative
    decoding: the draft proposes ``MXNET_SPEC_K`` tokens per step (or
    the draft config's ``"spec_k"``) and the target verifies them in
    one k+1-wide dispatch — greedy outputs stay bit-identical.
    ``--preload`` AOT-compiles every registered model's full program
    set BEFORE the port is bound, so ``/readyz`` never serves a cold
    replica."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="mxtpu-serve",
        description="serve exported models with dynamic batching over "
                    "shape-bucketed compiled engines")
    ap.add_argument("--model", action="append", default=[],
                    metavar="NAME=PREFIX[:EPOCH]",
                    help="register an exported model (repeatable)")
    ap.add_argument("--port", type=int, default=None,
                    help="HTTP port (default MXNET_SERVE_PORT or 8080; "
                         "0 picks an ephemeral port)")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="rows per coalesced dispatch "
                         "(default MXNET_SERVE_MAX_BATCH or 32)")
    ap.add_argument("--max-delay-ms", type=float, default=None,
                    help="batching deadline in ms "
                         "(default MXNET_SERVE_MAX_DELAY_MS or 5)")
    ap.add_argument("--queue", type=int, default=None,
                    help="bounded queue size before backpressure "
                         "(default MXNET_SERVE_QUEUE or 128)")
    ap.add_argument("--input-names", default="data",
                    help="comma-separated graph input names "
                         "(default 'data')")
    ap.add_argument("--input-specs", default=None,
                    metavar="D1,D2[;D1,...]",
                    help="per-example input shapes, batch dim excluded — "
                         "one comma-separated shape per input, "
                         "';'-separated (e.g. '784' or '3,224,224'); "
                         "required for --warmup")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every bucket before serving "
                         "(needs --input-specs; generation models warm "
                         "their prefill buckets and decode program)")
    ap.add_argument("--gen-model", action="append", default=[],
                    metavar="NAME=CONFIG.json",
                    help="register a generation model from a JSON "
                         "config (architecture kwargs + 'params' "
                         "weights path); repeatable")
    ap.add_argument("--gen-slots", type=int, default=None,
                    help="KV-cache slots per generation model (default "
                         "config or MXNET_GEN_MAX_SLOTS or 8)")
    ap.add_argument("--gen-max-len", type=int, default=None,
                    help="KV-cache sequence capacity (default config or "
                         "MXNET_GEN_MAX_LEN or the model's max_length)")
    ap.add_argument("--gen-block-size", type=int, default=None,
                    help="tokens per paged KV block (default "
                         "MXNET_KV_BLOCK_SIZE or 16)")
    ap.add_argument("--gen-scan-steps", type=int, default=None,
                    help="decode steps captured per scanned burst "
                         "dispatch, 0 disables the burst program "
                         "(default config or MXNET_DECODE_SCAN_STEPS "
                         "or 8)")
    ap.add_argument("--gen-draft", action="append", default=[],
                    metavar="NAME=CONFIG.json",
                    help="attach a draft model to generation model NAME "
                         "for speculative decoding (k from the config's "
                         "'spec_k' or MXNET_SPEC_K, default 4); "
                         "repeatable")
    ap.add_argument("--preload", action="store_true",
                    help="AOT-compile every model's full program set "
                         "(all buckets, decode, and the speculative "
                         "verify program) before binding the port — "
                         "/readyz never serves a cold replica")
    ns = ap.parse_args()
    if not ns.model and not ns.gen_model:
        ap.error("at least one --model NAME=PREFIX[:EPOCH] or "
                 "--gen-model NAME=CONFIG.json is required")
    input_specs = None
    if ns.input_specs is not None:
        input_specs = [tuple(int(d) for d in part.split(",") if d)
                       for part in ns.input_specs.split(";")]
    if ns.warmup and input_specs is None:
        ap.error("--warmup needs --input-specs (per-example shapes) to "
                 "synthesize bucket batches")

    from .base import getenv_int
    from .compile_cache import ensure_compile_cache
    from .serving import InferenceEngine, ModelServer

    # before anything compiles: parameter init below already does
    cache_dir = ensure_compile_cache()
    sys.stderr.write(f"mxtpu-serve: compile cache at {cache_dir}\n")
    batcher_kw = {}
    if ns.max_batch is not None:
        batcher_kw["max_batch_size"] = ns.max_batch
    if ns.max_delay_ms is not None:
        batcher_kw["max_delay_ms"] = ns.max_delay_ms
    if ns.queue is not None:
        batcher_kw["queue_size"] = ns.queue
    srv = ModelServer(port=ns.port, host=ns.host, **batcher_kw)
    input_names = [s for s in ns.input_names.split(",") if s]
    for spec in ns.model:
        name, _, ref = spec.partition("=")
        if not name or not ref:
            ap.error(f"--model wants NAME=PREFIX[:EPOCH], got {spec!r}")
        prefix, _, epoch = ref.rpartition(":")
        if not prefix or not epoch.isdigit():
            prefix, epoch = ref, "0"
        engine = InferenceEngine.from_export(
            prefix, int(epoch), input_names=input_names,
            input_specs=input_specs,
            max_batch_size=ns.max_batch
            or getenv_int("MXNET_SERVE_MAX_BATCH", 32),
            name=name)
        srv.add_model(name, engine, warmup=ns.warmup)
        sys.stderr.write(f"mxtpu-serve: loaded {name} from {prefix} "
                         f"(epoch {int(epoch)}, buckets "
                         f"{list(engine.buckets)})\n")
    drafts = {}
    for spec in ns.gen_draft:
        name, _, cfg_path = spec.partition("=")
        if not name or not cfg_path:
            ap.error(f"--gen-draft wants NAME=CONFIG.json, got {spec!r}")
        drafts[name] = cfg_path
    gen_names = {spec.partition("=")[0] for spec in ns.gen_model}
    for name in drafts:
        if name not in gen_names:
            ap.error(f"--gen-draft {name}: no matching --gen-model")
    for spec in ns.gen_model:
        name, _, cfg_path = spec.partition("=")
        if not name or not cfg_path:
            ap.error(f"--gen-model wants NAME=CONFIG.json, got {spec!r}")
        engine = _load_generation_engine(
            name, cfg_path, max_slots=ns.gen_slots,
            max_len=ns.gen_max_len, block_size=ns.gen_block_size,
            scan_steps=ns.gen_scan_steps)
        if name in drafts:
            # the draft mirrors the target's slot/sequence geometry so
            # its cache rolls back in lock-step with the target's
            draft = _load_generation_engine(
                name + "-draft", drafts[name],
                max_slots=engine.max_slots, max_len=engine.max_len,
                block_size=engine.block_size)
            engine.attach_draft(
                draft, spec_k=getattr(draft, "_cfg_spec_k", None))
            sys.stderr.write(
                f"mxtpu-serve: attached draft to {name} from "
                f"{drafts[name]} (spec_k {engine.spec_k})\n")
        srv.add_model(name, engine, warmup=ns.warmup)
        kv = f"paged blocks={engine.num_blocks - 1}x{engine.block_size}"
        sys.stderr.write(
            f"mxtpu-serve: loaded generation model {name} from "
            f"{cfg_path} (slots {engine.max_slots}, max_len "
            f"{engine.max_len}, kv {kv}, prefill buckets "
            f"{list(engine.prefill_buckets)})\n")
    if ns.preload:
        sys.stderr.write("mxtpu-serve: preloading — compiling all "
                         "programs before binding the port...\n")
        t0 = time.time()
        srv.preload()
        sys.stderr.write(f"mxtpu-serve: preload done in "
                         f"{time.time() - t0:.1f}s\n")
    srv.start()
    sys.stderr.write(f"mxtpu-serve: listening on "
                     f"http://{ns.host}:{srv.port} "
                     f"(/v1/models, /healthz, /readyz, /metrics)\n")
    from .serving import lifecycle
    sys.exit(lifecycle.run_until_shutdown(srv))


def supervise_main():
    """``mxtpu-supervise`` — self-healing serve fleet: supervise
    ``mxtpu-serve`` replica processes behind an embedded router, with
    crash/hang detection, restart-with-backoff, flap quarantine, and
    signal-driven autoscaling (docs/robustness.md "Self-healing
    fleet")::

        mxtpu-supervise --replicas 2 --min-replicas 1 --max-replicas 4 \\
                        [--router-port N] [--compile-cache DIR]
                        [--log-dir DIR] [--no-autoscale]
                        [--autoscale-interval F]
                        -- --gen-model g=/models/gpt.json --preload

    Everything after ``--`` is passed to each ``mxtpu-serve`` replica
    verbatim (do NOT pass ``--port``/``--host`` there — the supervisor
    allocates a port per replica slot and binds replicas to
    127.0.0.1).  ``--command`` replaces the replica command wholesale
    with a shell-split template whose ``{port}`` placeholder receives
    the slot port (drills supervise arbitrary servers this way).
    Knobs default from ``MXNET_SUPERVISE_*`` / ``MXNET_AUTOSCALE_*``
    (docs/env_var.md)."""
    import argparse
    import shlex

    argv = sys.argv[1:]
    serve_args: list = []
    if "--" in argv:
        split = argv.index("--")
        argv, serve_args = argv[:split], argv[split + 1:]

    ap = argparse.ArgumentParser(
        prog="mxtpu-supervise",
        description="supervise + autoscale an mxtpu-serve fleet behind "
                    "an embedded mxtpu-router")
    ap.add_argument("--replicas", type=int, default=1,
                    help="initial fleet size (default 1; raised to "
                         "--min-replicas if smaller)")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscale floor (default "
                         "MXNET_AUTOSCALE_MIN_REPLICAS or 1)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscale ceiling (default "
                         "MXNET_AUTOSCALE_MAX_REPLICAS or 4)")
    ap.add_argument("--router-port", type=int, default=0,
                    help="router listen port (default 0: ephemeral)")
    ap.add_argument("--compile-cache", metavar="DIR", default=None,
                    help="exported to every replica as "
                         "JAX_COMPILATION_CACHE_DIR — scale-up cold "
                         "starts reuse warm compiled artifacts (default: "
                         "the inherited variable, else each replica's "
                         "in-checkout .jax_cache)")
    ap.add_argument("--log-dir", metavar="DIR", default=None,
                    help="per-replica stdout/stderr logs land here "
                         "(default: discarded)")
    ap.add_argument("--no-autoscale", action="store_true",
                    help="supervise a fixed-size fleet (restarts and "
                         "quarantine only)")
    ap.add_argument("--autoscale-interval", type=float, default=None,
                    help="seconds between policy evaluations (default "
                         "MXNET_AUTOSCALE_INTERVAL_SECONDS or 10)")
    ap.add_argument("--command", default=None,
                    help="replica command template with a {port} "
                         "placeholder (shell-split; replaces the "
                         "default mxtpu-serve invocation)")
    ns = ap.parse_args(argv)
    if ns.command is not None and serve_args:
        ap.error("--command and '-- <mxtpu-serve args>' are exclusive")
    if ns.command is None and not serve_args:
        ap.error("replica command missing: pass '-- <mxtpu-serve args>' "
                 "or --command 'prog --port {port}'")

    from .serving import AutoscalePolicy, Supervisor, lifecycle

    if ns.command is not None:
        command = shlex.split(ns.command)
    else:
        # re-enter this interpreter's serve_main so the supervisor works
        # from a source checkout without installed console scripts
        command = [sys.executable, "-c",
                   "from incubator_mxnet_tpu._cli import serve_main; "
                   "serve_main()"] + serve_args \
            + ["--host", "127.0.0.1", "--port", "{port}"]
    child_env = {}
    if ns.compile_cache is not None:
        child_env["JAX_COMPILATION_CACHE_DIR"] = ns.compile_cache
    policy = AutoscalePolicy(min_replicas=ns.min_replicas,
                             max_replicas=ns.max_replicas)
    sup = Supervisor(command, replicas=ns.replicas, policy=policy,
                     autoscale=not ns.no_autoscale,
                     router_port=ns.router_port,
                     child_env=child_env, log_dir=ns.log_dir,
                     autoscale_interval_seconds=ns.autoscale_interval)
    sup.start()
    sys.stderr.write(
        f"mxtpu-supervise: router on http://0.0.0.0:{sup.router.port} "
        f"over {sup.alive_count()} replica(s); autoscale "
        f"{'off' if ns.no_autoscale else 'on'} "
        f"[{policy.min_replicas}, {policy.max_replicas}]\n")
    sys.exit(lifecycle.run_until_shutdown(sup))


def router_main():
    """``mxtpu-router`` — fault-tolerant front tier over a fleet of
    ``mxtpu-serve`` replicas (see docs/serving.md "Serving a fleet")::

        mxtpu-router --replica 127.0.0.1:8080 --replica 127.0.0.1:8081 \\
                     [--port N] [--retries N] [--health-interval F]
                     [--no-affinity] [--spill-margin N]
                     [--upstream-timeout F]

    Spreads ``POST /v1/models/<name>:predict`` / ``:generate`` over the
    replicas with health-aware least-loaded balancing, breaker-based
    outlier ejection, retry-with-failover (honoring ``Retry-After``),
    SSE passthrough, rendezvous-hash prefix-affine routing, and
    ``POST /admin/drain`` / ``/admin/undrain`` for zero-downtime
    rolling weight updates.  Knobs default from ``MXNET_ROUTER_*``
    (docs/env_var.md)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="mxtpu-router",
        description="route :predict/:generate over mxtpu-serve "
                    "replicas with failover, drains, and "
                    "prefix-affine balancing")
    ap.add_argument("--replica", action="append", default=[],
                    metavar="HOST:PORT",
                    help="an mxtpu-serve replica (repeatable; also "
                         "accepts a comma-separated list)")
    ap.add_argument("--port", type=int, default=None,
                    help="listen port (default MXNET_ROUTER_PORT or "
                         "8081; 0 picks an ephemeral port)")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--retries", type=int, default=None,
                    help="upstream attempts beyond the first per "
                         "request (default MXNET_ROUTER_RETRIES or 2)")
    ap.add_argument("--health-interval", type=float, default=None,
                    help="seconds between /readyz+/slo polls (default "
                         "MXNET_ROUTER_HEALTH_INTERVAL_SECONDS or 0.5)")
    ap.add_argument("--no-affinity", action="store_true",
                    help="disable rendezvous-hash prefix-affine "
                         "routing for :generate")
    ap.add_argument("--spill-margin", type=int, default=None,
                    help="inflight excess over the fleet minimum at "
                         "which an affinity owner spills (default "
                         "MXNET_ROUTER_SPILL_MARGIN or 8)")
    ap.add_argument("--upstream-timeout", type=float, default=None,
                    help="per-attempt upstream timeout in seconds "
                         "(default MXNET_ROUTER_UPSTREAM_TIMEOUT_"
                         "SECONDS or 10)")
    ns = ap.parse_args()
    replicas = [r for spec in ns.replica
                for r in spec.split(",") if r.strip()]
    if not replicas:
        ap.error("at least one --replica HOST:PORT is required")

    from .serving import Router, lifecycle

    router = Router(replicas, port=ns.port, host=ns.host,
                    retries=ns.retries,
                    health_interval=ns.health_interval,
                    affinity=False if ns.no_affinity else None,
                    spill_margin=ns.spill_margin,
                    upstream_timeout=ns.upstream_timeout)
    router.start()
    sys.stderr.write(
        f"mxtpu-router: listening on http://{ns.host}:{router.port} "
        f"over {len(router.replicas)} replica(s) "
        f"({', '.join(r.id for r in router.replicas)})\n")
    sys.exit(lifecycle.run_until_shutdown(router))
