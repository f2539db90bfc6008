"""The second kind of per-layer state (``docs/serving.md`` "The layer
interface"): rows of a recurrent layer's state beside the paged KV pool — a
join starts from zeros or from a snapshot, a leave frees the row, a padded
prompt position, a free slot and a slot that is ``done`` inside a burst leave
a row bit for bit, snapshots and blocks are evicted together, a hit never
ends past a snapshot, speculation is refused.
"""
import json
import os
import sys

import numpy as np
import pytest

CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

from reference import qwen3next as ref                  # noqa: E402
from programs import qwen3next_serve as prog            # noqa: E402

from incubator_mxnet_tpu.base import MXNetError         # noqa: E402
from incubator_mxnet_tpu.serving import (               # noqa: E402
    ContinuousBatcher, GenerationEngine)
from incubator_mxnet_tpu.serving.kvcache import (       # noqa: E402
    NO_SNAPSHOTS, BlockPool, KVLayout)

S = 3


def _engine(**kw):
    with open(os.path.join(CHIP, "tests", "tiny_qwen3next.json")) as f:
        cfg = json.load(f)
    net = prog.build_net(cfg)
    prog.load_weights(net, ref.init_params(cfg, 7))
    args = dict(name="tiny", max_slots=S, max_len=256,
                prefill_buckets=[64, 192], block_size=16, scan_steps=8,
                logprobs_topn=cfg["vocab_size"], state_snapshot_tokens=64,
                state_snapshot_rows=8)
    args.update(kw)
    return GenerationEngine(net, **args), cfg["vocab_size"]


def _rows(eng):
    return [np.asarray(a) for a in eng._recur]


def _prompt(V, n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, V, n)]


def test_the_layout_names_both_kinds_of_state():
    eng, _ = _engine()
    lay = eng.layout
    assert lay.kv_layers == (3,) and eng._state_layers == (0, 1, 2)
    assert [s is None for s in lay.states] == [False, False, False, True]
    assert lay.states[0] == (((4, 16, 16), "float32"), ((3, 128), "float32"))
    # one K and one V pool (the full layer's), two leaves a DeltaNet layer;
    # rows: 3 slots + 8 snapshots + the null row
    assert len(eng._cache) == 2 and len(eng._recur) == 6
    assert {a.shape[0] for a in eng._recur} == {S + 8 + 1}
    assert lay.block_bytes(16) == 2 * 1 * 1 * 16 * 32 * 4
    assert eng.pool.block_bytes == lay.block_bytes(16)
    st = eng.kv_stats()
    assert st["state_rows_total"] == S + 8 and st["state_rows_in_use"] == 0
    assert st["state_bytes"] == sum(a.nbytes for a in _rows(eng))
    # a model that states no state has none
    plain = KVLayout.of({"num_layers": 2, "kv_heads": 1, "head_dim": 8,
                         "dtype": "float32", "windows": (None, None),
                         "max_length": 64})
    assert plain.states == (None, None) and plain.kv_layers == (0, 1)
    with pytest.raises(MXNetError):
        KVLayout.of({"num_layers": 2, "kv_heads": 1, "head_dim": 8,
                     "dtype": "float32", "windows": (None, None),
                     "max_length": 64, "states": (None,)})


def test_a_join_starts_from_zeros_and_a_leave_frees_the_row():
    eng, V = _engine(prefix_cache=False)
    fresh, _ = _engine(prefix_cache=False)
    eng.prefill(_prompt(V, 100, 1), 0, reserve_tokens=130)
    eng.decode(np.zeros(S, np.int32), np.asarray([100, 0, 0], np.int32))
    assert eng.kv_stats()["state_rows_in_use"] == 1
    eng.release_slot(0)
    assert eng.kv_stats()["state_rows_in_use"] == 0
    # the row still holds the last request's state; the next join must not
    assert np.abs(_rows(eng)[0][0]).max() > 0
    other = _prompt(V, 90, 2)
    assert eng.prefill(other, 0, reserve_tokens=120) \
        == fresh.prefill(other, 0, reserve_tokens=120)
    for a, b in zip(_rows(eng), _rows(fresh)):
        np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(eng.last_prefill_logprobs()[0],
                                  fresh.last_prefill_logprobs()[0])


def test_padded_positions_leave_the_state_as_it_is():
    """70 tokens in a bucket of 192 (two chunks of padding) and in one of 72
    leave the same row, tail and matrices alike."""
    a, V = _engine(prefill_buckets=[192], prefix_cache=False)
    b, _ = _engine(prefill_buckets=[72], prefix_cache=False)
    p = _prompt(V, 70, 3)
    assert a.prefill(p, 1, reserve_tokens=100) \
        == b.prefill(p, 1, reserve_tokens=100)
    for x, y in zip(_rows(a), _rows(b)):
        np.testing.assert_array_equal(x[1], y[1])
        assert np.abs(x[1]).max() > 0


def test_dead_and_done_slots_keep_their_rows_through_a_burst():
    """Slot 0 runs all 8 steps, slot 1 is done after 3 of them, slot 2
    holds a request but is handed in as not active: row 2 comes back bit
    for bit, row 1 as three single steps leave it; the rows past the
    slots' are not touched."""
    eng, V = _engine()
    twin, _ = _engine()
    firsts = []
    for e in (eng, twin):
        firsts = [e.prefill(_prompt(V, 80 + 10 * s, 10 + s), s,
                            reserve_tokens=130) for s in range(S)]
    before = _rows(eng)
    last = np.asarray(firsts, np.int32)
    pos = np.asarray([80, 90, 100], np.int32)
    toks, emitted = eng.decode_burst(
        last, pos, np.asarray([8, 3, 8], np.int32), np.full(S, -1, np.int32),
        np.asarray([True, True, False]))
    assert emitted.tolist() == [8, 3, 0]
    after = _rows(eng)
    lt, pv = last.copy(), pos.copy()
    for j in range(3):
        nxt = twin.decode(lt, pv)
        assert nxt[1] == toks[j, 1] and nxt[0] == toks[j, 0]
        lt, pv = nxt.astype(np.int32), pv + 1
    for x, y, z in zip(before, after, _rows(twin)):
        np.testing.assert_array_equal(y[2], x[2])       # not active
        np.testing.assert_array_equal(y[S:], x[S:])     # snapshots, null
        assert not np.array_equal(y[0], x[0])
        np.testing.assert_allclose(y[1], z[1], atol=1e-6, rtol=1e-6)
        assert not np.array_equal(y[1], x[1])
    # a single step over a free slot (its table is all null block)
    eng.release_slot(2)
    before = _rows(eng)
    eng.decode(np.zeros(S, np.int32), np.asarray([88, 93, 0], np.int32))
    for x, y in zip(before, _rows(eng)):
        np.testing.assert_array_equal(y[2], x[2])


def test_reset_drops_states_with_blocks():
    eng, V = _engine()
    p = _prompt(V, 150, 4)
    eng.prefill(p, 0, reserve_tokens=170)
    # nobody had sent that prefix: blocks registered, no row spent on it
    assert eng.pool.snapshots_in_use == 0
    eng.prefill(p, 1, reserve_tokens=170)       # seen before: 64 and 128
    assert eng.pool.snapshots_in_use == 2 and eng.pool.hits == 0
    assert eng.kv_stats()["state_rows_in_use"] == 4
    eng.reset()
    assert eng.pool.snapshots_in_use == 0
    assert eng.kv_stats()["state_rows_in_use"] == 0
    assert all(np.abs(a).max() == 0 for a in _rows(eng))


def test_attach_draft_raises_with_the_reason():
    eng, _ = _engine()
    draft, _ = _engine(name="draft")
    with pytest.raises(MXNetError, match="cannot be moved back"):
        eng.attach_draft(draft, spec_k=2)


# -- the pool's side, without a model ----------------------------------------
def _pool(rows=4, blocks=17):
    return BlockPool(blocks, 16, snapshot_every=32, snapshot_rows=rows,
                     first_snapshot_row=100, model="t")


def test_a_hit_never_ends_past_a_snapshot():
    pool = _pool(blocks=65)
    A = list(range(70))
    table, m, plan = pool.allocate(A, 70, 80)
    # nobody has sent any of A before: its blocks are registered and no row
    # is spent on a prefix nothing may ever hit
    assert m == 0 and plan == NO_SNAPSHOTS and pool.snapshots_in_use == 0
    # A2 shares 60 tokens: three of A's blocks match and none ends at a
    # snapshot, so A2 prefills whole — and keeps the state at 32, where a
    # block of A's ends (64 closes a block of its own tokens)
    ta, m, plan = pool.allocate(A[:60] + [998] * 10, 70, 80)
    assert m == 0 and plan == (None, {32: 100}) and not set(ta) & set(table)
    assert pool._snap_of == {table[1]: 100} and pool.hits == 0
    # B shares the same 60: three cached blocks match, the hit is the two
    # that end at the snapshot
    B = A[:60] + [999] * 10
    assert pool.can_admit(B, 70, 80)
    tb, m, plan = pool.allocate(B, 70, 80)
    assert m == 32 and tb[:2] == table[:2] and tb[2] != table[2]
    assert plan == (100, {})
    # A again, whole: the hit ends at 32, and 64 has now been seen before
    _, m, plan = pool.allocate(A, 70, 80)
    assert m == 32 and plan == (100, {64: 101})
    assert pool.snapshots_restored == 2 and pool.snapshots_kept == 2
    # a prompt that ends ON a snapshot boundary keeps a row to read from:
    # the hit stops one snapshot short
    _, m, plan = pool.allocate(A[:64], 64, 70)
    assert m == 32 and plan == (100, {})
    # when the rows run out the snapshot used longest ago goes (A's at 64:
    # the one at 32 was used by the hits), its blocks stay cached
    for first in (500, 600):
        for _ in range(2):
            pool.allocate(list(range(first, first + 40)), 40, 48)
    assert pool.snapshots_in_use == 4 and pool.snapshots_evicted == 0
    for _ in range(2):
        pool.allocate(list(range(700, 740)), 40, 48)
    assert pool.snapshots_evicted == 1
    assert table[3] not in pool._snap_of and table[1] in pool._snap_of
    _, m, _ = pool.allocate(A + [7], 71, 80)
    assert m == 32                  # four cached blocks, one snapshot left


def test_snapshots_and_blocks_are_evicted_together():
    pool = _pool(rows=4, blocks=17)                 # 16 blocks
    A = list(range(70))
    table, _, _ = pool.allocate(A, 70, 80)          # 5 blocks, 4 registered
    again, _, plan = pool.allocate(A, 70, 80)       # 5 of its own
    assert plan == (None, {32: 100, 64: 101})       # under A's first blocks
    pool.release(table)
    pool.release(again)
    assert pool.snapshots_in_use == 2 and pool.free_blocks == 16
    # 14 fresh blocks: the 12 free ones, then A's idle blocks oldest first
    other, m, plan = pool.allocate(list(range(200, 420)), 220, 224)
    assert m == 0 and plan == NO_SNAPSHOTS and pool.evictions == 2
    # A's blocks 0 and 1 went, and with block 1 the snapshot at 32; block 3
    # and its snapshot at 64 are still there and are no hit without them
    assert pool.snapshots_evicted == 1
    assert table[3] in pool._snap_of and table[1] not in pool._snap_of
    pool.release(other)
    assert pool.allocate(A, 70, 80)[1] == 0
    # a failed prefill's planned snapshots were never written: they go with
    # its blocks, and the older prompt's blocks stay what they were
    pool2 = _pool()
    pool2.allocate(A, 70, 80)
    t2, _, plan = pool2.allocate(A, 70, 80)
    assert sorted(plan.keep) == [32, 64] and pool2.snapshots_in_use == 2
    pool2.invalidate(t2, plan)
    assert pool2.snapshots_in_use == 0 and pool2.snapshots_evicted == 2
    pool2.release(t2)
    _, m, plan = pool2.allocate(A, 70, 80)
    assert m == 0 and sorted(plan.keep) == [32, 64]
    with pytest.raises(ValueError):
        BlockPool(9, 16, snapshot_every=40, snapshot_rows=2)


def test_state_stats_reach_the_batcher():
    """Three requests that share 128 tokens through the continuous batcher:
    the second finds the first's blocks and keeps the snapshots, the third
    joins by snapshot, in the one prefill dispatch."""
    eng, V = _engine(logprobs_topn=0)
    assert eng.warmup() == eng.expected_programs == 7
    assert eng.kv_stats()["prefill_tokens"] == {"miss": 0, "hit": 0,
                                                "prefix_hit": 0}
    shared = _prompt(V, 128, 5)
    bat = ContinuousBatcher(eng, name="tiny")
    try:
        for own in (_prompt(V, 30, 6), _prompt(V, 20, 8)):
            a = bat.submit_async(shared + own, max_new_tokens=12)
            assert len(a.result(120)) == 12
        before = eng.program_inventory()["programs"]
        b = bat.submit_async(shared + _prompt(V, 40, 7), max_new_tokens=12)
        assert len(b.result(120)) == 12
        st = bat.stats()
        after = eng.program_inventory()["programs"]
    finally:
        bat.close()
    assert st["prefill_tokens"] == {"miss": 158 + 148, "hit": 40,
                                    "prefix_hit": 128}
    assert st["state_snapshots_restored"] == 1
    assert st["state_snapshots_kept"] == 2 and st["state_rows_total"] == 11
    assert st["state_rows_in_use"] == 2             # the snapshots; no slot

    def n(rows, name):
        return rows.get("serving:tiny:" + name, {}).get("dispatches", 0)
    # the join is ONE prefill dispatch, the hit program's: the snapshot is
    # read by row inside it
    assert n(after, "prefill_ext") - n(before, "prefill_ext") == 1
    assert n(after, "prefill") == n(before, "prefill")
    assert set(after) == set(before)                # and no other program
