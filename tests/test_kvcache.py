"""Paged KV-cache subsystem tests: BlockPool invariants (alloc / free /
refcount / copy-on-write / LRU eviction), the GenerationEngine's
token-for-token parity against the cache-free oracle (solo, prefix-hit,
and mid-flight join through the ContinuousBatcher), prefix-cache FLOPs
savings measured on the ``XLA_COST`` plane, the closed compiled-program
set, pool-rewipe on ``reset()``, the paged Pallas gather's
interpret-mode parity, and capacity backpressure on the HTTP surface
(429 + ``Retry-After``)."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from common import greedy_reference

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (BlockPool, ContinuousBatcher,
                                         GenerationEngine, ModelServer,
                                         blocks_for)
from incubator_mxnet_tpu.serving import slo as _slo


@pytest.fixture(autouse=True)
def _clean_state():
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    _slo.tracker.reset()
    yield
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    _slo.tracker.reset()


def _gpt(max_length=64, seed=3):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64,
                   num_layers=2, num_heads=2, max_length=max_length,
                   dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))   # settle shapes
    return net


def _pair(max_slots=4, max_len=64, seed=3, **paged_kw):
    """A model — whose cache-free ``greedy_reference`` is the oracle —
    and the engine under test over it."""
    net = _gpt(max_length=max_len, seed=seed)
    paged = GenerationEngine(net, name="paged", max_slots=max_slots,
                             max_len=max_len, **paged_kw)
    return net, paged


# ------------------------------------------------------ pool invariants
def test_blocks_for():
    assert blocks_for(0, 16) == 0
    assert blocks_for(1, 16) == 1
    assert blocks_for(16, 16) == 1
    assert blocks_for(17, 16) == 2


def test_pool_alloc_release_refcounts():
    pool = BlockPool(9, 16, model="t")            # 8 allocatable
    toks = list(range(40))
    table, m, _ = pool.allocate(toks, 40, 48)        # 3 blocks, cold
    assert m == 0 and len(table) == 3
    assert 0 not in table                         # null block never leaves
    assert pool.blocks_in_use == 3
    assert all(pool.refcount(b) == 1 for b in table)
    pool.release(table)
    # blocks 0 and 1 covered full prompt blocks -> cached idle, block 2
    # was the mutable tail -> straight back to the free list
    assert pool.blocks_in_use == 0
    assert pool.free_blocks == 8
    with pytest.raises(MXNetError):
        pool.release(table)                       # double free


def test_pool_prefix_sharing_and_refcounts():
    pool = BlockPool(17, 16, model="t")
    toks = list(range(40))                        # 2 full blocks shareable
    t1, m1, _ = pool.allocate(toks, 40, 64)
    assert m1 == 0
    t2, m2, _ = pool.allocate(toks, 40, 64)
    assert m2 == 32                               # both full blocks shared
    assert t2[:2] == t1[:2]                       # same physical blocks
    assert t2[2:] != t1[2:]
    assert pool.refcount(t1[0]) == 2 and pool.refcount(t1[1]) == 2
    assert pool.hits == 2
    pool.release(t1)
    assert pool.refcount(t2[0]) == 1              # survivor keeps them
    pool.release(t2)
    assert pool.blocks_in_use == 0
    assert pool.cached_blocks == 2                # still hittable
    t3, m3, _ = pool.allocate(toks, 40, 64)
    assert m3 == 32                               # idle cached blocks hit
    pool.release(t3)


def test_pool_prefix_cache_disabled():
    pool = BlockPool(17, 16, prefix_cache=False, model="t")
    toks = list(range(40))
    t1, m1, _ = pool.allocate(toks, 40, 64)
    t2, m2, _ = pool.allocate(toks, 40, 64)
    assert m1 == m2 == 0
    assert not set(t1) & set(t2)
    assert pool.hits == 0


def test_pool_copy_on_write():
    pool = BlockPool(9, 16, model="t")
    toks = list(range(40))
    t1, _, _ = pool.allocate(toks, 40, 48)
    # exclusively-owned mutable tail: no copy
    tail = t1[2]
    assert pool.copy_on_write(tail) == tail
    # exclusively-owned but published: unpublished in place, no copy
    pub = t1[1]
    assert pool.copy_on_write(pub) == pub
    assert pool.refcount(pub) == 1
    t2, m2, _ = pool.allocate(toks, 40, 48)
    assert m2 == 16                               # unpublished block misses
    shared = t1[0]
    assert pool.refcount(shared) == 2
    new = pool.copy_on_write(shared)
    assert new != shared                          # real copy when shared
    assert pool.refcount(shared) == 1
    assert pool.refcount(new) == 1
    assert pool.cow_copies == 1
    with pytest.raises(MXNetError):
        pool.copy_on_write(0)                     # unreferenced


def test_pool_exhaustion_and_can_admit():
    pool = BlockPool(5, 16, model="t")            # 4 allocatable
    toks = list(range(3))
    t1, _, _ = pool.allocate(toks, 3, 64)            # takes all 4
    assert not pool.can_admit([7] * 3, 3, 17)
    with pytest.raises(MXNetError):
        pool.allocate([7] * 3, 3, 17)
    pool.release(t1)
    assert pool.can_admit([7] * 3, 3, 17)
    # the reserved_blocks discount models earlier same-step admits
    assert not pool.can_admit([7] * 3, 3, 33, reserved_blocks=3)


def test_pool_lru_eviction_under_pressure():
    pool = BlockPool(5, 16, model="t")            # 4 allocatable
    a = pool.allocate(list(range(16)) + [1], 17, 17)[0]
    pool.release(a)                               # 1 cached idle
    b = pool.allocate(list(range(100, 116)) + [1], 17, 17)[0]
    pool.release(b)                               # 2 cached idle
    assert pool.cached_blocks == 2
    # demand 3+ fresh blocks: free list has 2, so the OLDEST idle cached
    # block (prompt a's) must be reclaimed
    c, m, _ = pool.allocate([9] * 50, 50, 64)
    assert m == 0
    assert pool.evictions >= 1
    # prompt a's block is gone from the cache; prompt b's may also have
    # been evicted depending on demand — re-allocating a must miss
    pool.release(c)
    t, m, _ = pool.allocate(list(range(16)) + [1], 17, 17)
    assert m == 0


def test_pool_shared_idle_blocks_not_double_counted():
    # Regression: a request that shares an IDLE cached block must not
    # also count that block as reclaimable capacity for its fresh tail.
    # The old check passed, then allocate() raised mid-mutation in
    # _pop_free and leaked the partially-built table.
    pool = BlockPool(9, 16, model="t")            # 8 allocatable
    toks = list(range(16)) + [1]
    a = pool.allocate(toks, 17, 17)[0]            # 1 shareable + tail
    pool.release(a)                               # 1 idle cached, 7 free
    live = pool.allocate([9] * 50, 50, 64)[0]     # 4 blocks pinned
    assert pool.free_blocks == 4                  # 3 free + 1 idle
    # need 5 blocks, 1 shared (the idle one) -> 4 fresh, but only 3
    # blocks are truly available once the share pins the idle block
    assert not pool.can_admit(toks, 17, 65)
    with pytest.raises(MXNetError):
        pool.allocate(toks, 17, 65)
    # the failed allocate mutated nothing: no leaked refcounts/blocks
    assert pool.blocks_in_use == 4
    assert pool.free_blocks == 4
    assert pool.refcount(a[0]) == 0
    # 1 idle entry from prompt a + 3 full blocks of the live request
    assert pool.cached_blocks == 4
    # one block less and the same request fits, sharing the idle block
    assert pool.can_admit(toks, 17, 64)
    t, m, _ = pool.allocate(toks, 17, 64)
    assert m == 16 and t[0] == a[0]
    pool.release(t)
    pool.release(live)


def test_pool_invalidate_unregisters_prefix_entries():
    pool = BlockPool(9, 16, model="t")
    toks = list(range(40))
    t, _, _ = pool.allocate(toks, 40, 48)            # 2 full blocks registered
    assert pool.cached_blocks == 2
    pool.invalidate(t)
    assert pool.cached_blocks == 0
    assert all(pool.refcount(b) == 1 for b in t)  # refcounts untouched
    pool.release(t)
    assert pool.free_blocks == 8                  # all straight to free
    t2, m2, _ = pool.allocate(toks, 40, 48)
    assert m2 == 0                                # no hit on invalidated
    pool.release(t2)


def test_prefix_keys_are_collision_resistant():
    # hash(-1) == hash(-2) in CPython, so Python-hash-keyed prefix
    # caching would alias these two distinct prompts onto the same
    # blocks; content digests must keep them apart.
    pool = BlockPool(17, 16, model="t")
    t1, m1, _ = pool.allocate([-1] * 17, 17, 32)
    t2, m2, _ = pool.allocate([-2] * 17, 17, 32)
    assert m1 == 0 and m2 == 0                    # no bogus prefix hit
    assert not set(t1) & set(t2)
    assert pool.hits == 0
    pool.release(t1)
    pool.release(t2)


# ------------------------------------------- paged vs cache-free parity
def test_paged_solo_parity_token_for_token():
    net, paged = _pair()
    for prompt in ([9, 9, 4, 1], [3, 7, 11], list(range(1, 20)),
                   [2] * 33, [5] * 40):
        want = greedy_reference(net, prompt, 20)
        got = paged.generate(prompt, max_new_tokens=20)
        assert got == want, prompt
        paged.reset()


def test_paged_prefix_hit_parity_and_sharing():
    net, paged = _pair()
    prompt = [5] * 40
    want = greedy_reference(net, prompt, 12)
    first = paged.generate(prompt, max_new_tokens=12)
    hits0 = paged.pool.hits
    second = paged.generate(prompt, max_new_tokens=12)  # through the cache
    assert first == want
    assert second == want                     # hit path, same tokens
    assert paged.pool.hits - hits0 == 2       # both full prompt blocks


def test_paged_midflight_join_parity():
    net, paged = _pair()
    # A runs to the end of its 64 positions: on a loaded host the worker
    # got through 27 more tokens (four bursts) before this thread's next
    # 5 ms poll, and B joined an empty batch
    solo_a = greedy_reference(net, [9, 9, 4, 1], 56)
    solo_b = greedy_reference(net, [3, 7, 11], 8)
    bat = ContinuousBatcher(paged, name="paged")
    try:
        ra = bat.submit_async([9, 9, 4, 1], max_new_tokens=56)
        deadline = time.monotonic() + 10
        while len(ra.tokens_out) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        rb = bat.submit_async([3, 7, 11], max_new_tokens=8)
        assert ra.result(30) == solo_a
        assert rb.result(30) == solo_b
        assert bat.stats()["peak_slots_in_use"] >= 2
    finally:
        bat.close()


def test_closed_program_set_survives_hits_and_joins():
    _, paged = _pair()
    warmed = paged.warmup()
    # a miss and a hit prefill per bucket, decode, burst, the row edit
    assert warmed == paged.expected_programs \
        == 2 * len(paged.prefill_buckets) + 3
    n = paged.compiled_programs()
    paged.generate([4, 4, 4], max_new_tokens=8)
    paged.generate([2] * 17, max_new_tokens=8)
    paged.generate([2] * 17, max_new_tokens=8)    # prefix-hit program
    bat = ContinuousBatcher(paged, name="paged")
    try:
        ra = bat.submit_async([2] * 17, max_new_tokens=10)
        rb = bat.submit_async([6] * 40, max_new_tokens=10)
        ra.result(30)
        rb.result(30)
    finally:
        bat.close()
    assert paged.compiled_programs() == n         # still closed


def test_failed_prefill_does_not_poison_prefix_cache(monkeypatch):
    # Regression: allocate() registers full prompt blocks before the
    # prefill dispatch runs; if that dispatch fails, the never-written
    # blocks must be unregistered or a later same-prefix request would
    # "hit" blocks holding garbage K/V.
    net, paged = _pair()
    prompt = [5] * 40
    want = greedy_reference(net, prompt, 8)

    def boom(*a, **kw):
        raise RuntimeError("injected prefill failure")

    monkeypatch.setattr(paged, "_prefill_paged_dispatch", boom)
    with pytest.raises(RuntimeError):
        paged.prefill(prompt, 0)
    monkeypatch.undo()
    assert paged.pool.blocks_in_use == 0          # table released
    assert paged.pool.cached_blocks == 0          # nothing poisoned
    hits0 = paged.pool.hits
    assert paged.generate(prompt, max_new_tokens=8) == want
    assert paged.pool.hits == hits0               # prefilled cold


# -------------------------------------------- prefix cache saves prefill
def test_prefix_hit_cuts_prefill_flops():
    _, paged = _pair()
    events = []

    def on_cost(**kw):
        events.append(kw)

    telemetry.XLA_COST.subscribe(on_cost)
    try:
        prompt = [7] * 40                         # 2 shareable blocks

        def prefill_flops():
            return sum(e["flops"] for e in events
                       if "prefill" in e["where"])

        paged.generate(prompt, max_new_tokens=4)  # cold: full prefill
        cold = prefill_flops()
        events.clear()
        paged.generate(prompt, max_new_tokens=4)  # warm: suffix only
        warm = prefill_flops()
    finally:
        telemetry.XLA_COST.unsubscribe(on_cost)
    assert cold > 0 and warm > 0
    # 32 of 40 prompt tokens came from the cache; the suffix program
    # runs an 8-bucket forward instead of a 64-bucket one
    assert warm < 0.6 * cold, (cold, warm)


# ------------------------------------------------- engine-level eviction
def test_engine_eviction_under_pressure_stays_correct():
    # 5 blocks = 80 tokens: one 40-token request + cached leftovers
    # force LRU eviction on the next distinct prompt
    net, paged = _pair(max_slots=2, num_blocks=6)
    prompts = [[5] * 40, [9] * 40, [3] * 40, [5] * 40]
    for p in prompts:
        assert paged.generate(p, max_new_tokens=8) \
            == greedy_reference(net, p, 8), p
    assert paged.pool.evictions > 0


# ----------------------------------------------------- reset rewipes all
def test_reset_rewipes_tables_pool_and_prefix_cache():
    _, paged = _pair()
    paged.generate([5] * 40, max_new_tokens=8)
    paged.generate([5] * 40, max_new_tokens=8)
    assert paged.pool.hits > 0
    assert paged.pool.cached_blocks > 0
    paged.reset()
    assert paged.pool.free_blocks == paged.num_blocks - 1
    assert paged.pool.blocks_in_use == 0
    assert paged.pool.cached_blocks == 0          # stale K/V unreachable
    assert not np.any(paged._tables)
    assert all(not b for b in paged._slot_blocks)
    # and the engine still serves correctly afterwards
    out1 = paged.generate([5] * 40, max_new_tokens=8)
    paged.reset()
    out2 = paged.generate([5] * 40, max_new_tokens=8)
    assert out1 == out2


def test_watchdog_restart_rewipes_pool():
    from incubator_mxnet_tpu.serving import CircuitBreaker
    _, paged = _pair(max_slots=2, max_len=128)
    # short breaker cooldown so the post-restart probe is admitted
    bat = ContinuousBatcher(paged, name="paged",
                            breaker=CircuitBreaker("paged",
                                                   cooldown_seconds=0.1))
    try:
        fault.install_plan("serving.infer:hang:30@5")
        req = bat.submit_async([3, 7, 11], max_new_tokens=100,
                               request_id="rider-1")
        deadline = time.monotonic() + 10
        while not req.tokens_out and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.1)
        assert bat.check_worker(hang_seconds=0.05) == "hung"
        with pytest.raises(Exception):
            req.result(timeout=30)
        fault.clear_plan()
        # the replacement worker resets the engine: pool fully free
        deadline = time.monotonic() + 5
        while (bat.slots_in_use() or paged.pool.blocks_in_use) \
                and time.monotonic() < deadline:
            time.sleep(0.005)   # the reset runs outside the batcher's lock
        assert paged.pool.blocks_in_use == 0
        assert paged.pool.cached_blocks == 0
        # first request after the cooldown is the breaker's probe
        deadline = time.monotonic() + 5
        while True:
            try:
                r2 = bat.submit_async([3, 7, 11], max_new_tokens=5)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.05)
        assert len(r2.result(30)) == 5
    finally:
        fault.clear_plan()
        bat.close()


# ------------------------------------------- paged Pallas gather parity
def test_paged_pallas_kernel_interpret_parity(monkeypatch):
    from incubator_mxnet_tpu.kernels.flash_attention import (
        _paged_verify_pallas, _xla_paged_decode_attention,
        paged_decode_attention)
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    S, H, bs, D, NBLK, NB = 3, 2, 16, 16, 12, 4
    kp = jnp.asarray(rng.randn(NBLK, H, bs, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(NBLK, H, bs, D).astype(np.float32))
    q = jnp.asarray(rng.randn(S, H, D).astype(np.float32))
    tables = jnp.asarray(rng.randint(0, NBLK, (S, NB)).astype(np.int32))
    positions = jnp.asarray(np.array([5, 30, 63], np.int32))
    ref = _xla_paged_decode_attention(q, kp, vp, tables, positions, 0.25)
    # single-query decode is the verify kernel at query width 1
    out = _paged_verify_pallas(q[:, :, None, :], kp, vp, tables, positions,
                               0.25, interpret=True)[:, :, 0, :]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # the dispatch honors the force knob (interpret mode on CPU)
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    out2 = paged_decode_attention(q, kp, vp, tables, positions, scale=0.25)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_engine_parity_with_forced_pallas_decode(monkeypatch):
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    net, paged = _pair(max_slots=2)
    want = greedy_reference(net, [3, 7, 11], 8)
    got = paged.generate([3, 7, 11], max_new_tokens=8)
    assert paged.program_inventory()["paged_attention"] == "pallas"
    # interpreted-kernel fp differs from lax at the ulp level; greedy
    # argmax must still agree token-for-token
    assert got == want


# --------------------------------- HTTP backpressure: 429 + Retry-After
def test_http_429_retry_after_on_pool_exhaustion():
    net = _gpt()
    # one slot, pool sized for exactly one max-length request: the
    # capacity-aware queue bound admits 4x1 waiters, the 6th submit
    # must be rejected, not queued unboundedly
    eng = GenerationEngine(net, name="g", max_slots=1, max_len=64,
                           paged=True, num_blocks=5)
    srv = ModelServer(port=0)
    srv.add_model("g", eng)
    srv.start()
    url = f"http://127.0.0.1:{srv.port}/v1/models/g:generate"
    try:
        # wedge the worker mid-decode so submissions pile up
        fault.install_plan("serving.infer:hang:3@2")

        def post(budget=60):
            req = urllib.request.Request(url, data=json.dumps(
                {"tokens": [1, 2, 3], "max_new_tokens": budget,
                 "stream": True}).encode())
            return urllib.request.urlopen(req, timeout=30)

        streams = [post()]                    # occupies the slot
        time.sleep(0.3)                       # hang engages
        for _ in range(4):
            streams.append(post())            # fill the admitted queue
        with pytest.raises(urllib.error.HTTPError) as ei:
            post()
        assert ei.value.code == 429
        retry = ei.value.headers.get("Retry-After")
        assert retry is not None and int(retry) >= 1
        body = json.loads(ei.value.read())
        assert "backpressure" in body["error"]
        ei.value.close()
        prom = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10
        ).read().decode()
        assert "mxtpu_serve_rejected" in prom
        assert "mxtpu_kv_blocks_in_use" in prom
        assert "mxtpu_kv_blocks_total" in prom
        fault.clear_plan()
        for s in streams:
            s.read()                          # drain to completion
            s.close()
        # per-model cache utilization on GET /v1/models
        stats = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/models", timeout=10))
        g = stats["models"]["g"]
        assert g["kv_blocks_total"] == 4
        assert "kv_utilization" in g
    finally:
        fault.clear_plan()
        srv.stop()


def test_the_dense_mode_is_gone():
    """The constructor keeps ``paged`` only until benchmark/chip stops
    passing it: True and None mean the one mode there is, False is
    refused."""
    net = _gpt()
    with pytest.raises(MXNetError, match="dense KV mode is gone"):
        GenerationEngine(net, name="g", max_slots=2, max_len=64,
                         paged=False)
    eng = GenerationEngine(net, name="g", max_slots=2, max_len=64,
                           paged=True)
    assert eng.pool is not None and "kv_blocks_total" in eng.kv_stats()


# -- what the grouped paged kernel leans on: the pool hands blocks out in a
# -- row, and nothing the pool does multiplies the joints --------------------
def _joints(ids):
    """Neighbours in ``ids`` that are not consecutive block ids."""
    ids = list(ids)
    return sum(1 for a, b in zip(ids, ids[1:]) if b != a + 1)


@pytest.mark.parametrize("order", ["admission", "random"])
@pytest.mark.parametrize("hashed", [False, True],
                         ids=["unhashed", "hashed"])
def test_tables_are_runs_of_consecutive_blocks(hashed, order):
    """Six live tables of 15-100 blocks through a pool of 1,024, 300 joins:
    the FIFO wraps twenty times.  A table is cut off the head of the free
    list (then off the idle LRU's old end) and goes back whole and in table
    order, so a joint is only ever made where a piece goes back behind
    blocks it does not follow: at most one a release — two with hashed
    prompt blocks, which go back apart, to the idle LRU — and one a join
    that passes from the free list to the LRU.  Nothing multiplies them:
    over the free list, the LRU and the live tables they stay under that
    count; released in admission order and unhashed there is ONE, the
    wrap's, and every table is one run or two."""
    rng = np.random.default_rng(5)
    pool = BlockPool(1025, 4, prefix_cache=hashed, model="t")
    live, releases, joins = [], 0, 0
    for _ in range(300):
        while len(live) >= 6:
            at = 0 if order == "admission" else int(rng.integers(len(live)))
            pool.release(live.pop(at))
            releases += 1
        n = int(rng.integers(40, 200))
        table, shared, _ = pool.allocate(
            rng.integers(0, 1000, n), n, n + int(rng.integers(20, 200)),
            share=hashed)
        assert shared == 0                       # unshared prompts
        joins += 1
        live.append(table)
        if releases == 0:                        # a fresh pool: all in a row
            assert _joints(table) == 0
        if not hashed and order == "admission":
            assert _joints(table) <= 1
        total = _joints(pool._free) + _joints(pool._idle) \
            + sum(_joints(t) for t in live)
        assert total <= (2 * releases + joins if hashed else releases + 1)
    assert releases > 250
