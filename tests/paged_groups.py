"""What ``mxtpu_paged_groups_total`` must read, by plain loops, and the
check of an engine against it that the three expert models' tests share
(``test_afmoe.py``, ``test_smallthinker.py``, ``test_qwen3_next.py``)."""
import numpy as _np


def paged_groups_by_loops(calls, windows, block_size, num_blocks,
                          group_keys, step_groups):
    """``{"run": n, "blocks": n}``: what ``mxtpu_paged_groups_total`` must
    read after the decode dispatches ``calls`` — ``[(tables (S, cols),
    positions (S,), steps (S,))]``, slot ``s`` live for ``steps[s]`` steps
    from write head ``positions[s]`` — over layers with ``windows`` (one
    entry a layer that takes the grouped paged kernel; None: no window):
    the kernel's work list by plain loops, a group ``group_keys`` keys, a
    kernel step ``step_groups`` groups."""
    out = {"run": 0, "blocks": 0}
    for tables, positions, steps in calls:
        n_cols = tables.shape[1]
        pages = min(max(1, group_keys // block_size), n_cols)
        per_step = min(step_groups, -(-n_cols // pages))
        for window in windows:
            for s in range(len(tables)):
                for k in range(int(steps[s])):
                    pos = int(positions[s]) + k
                    last = min(pos // block_size, n_cols - 1)
                    first = 0 if window is None else max(
                        pos - window + 1, 0) \
                        // (block_size * pages * per_step) * per_step
                    for g in range(first, last // pages + 1):
                        c = g * pages
                        ids = [int(t) for t in tables[s, c:last + 1][:pages]]
                        run = ids == list(range(ids[0], ids[0] + len(ids))) \
                            and ids[0] + pages <= num_blocks
                        out["run" if run else "blocks"] += 1
    return out


def turn_pool(eng, blocks):
    """Take ``blocks`` blocks off the pool's FIFO and give them back: the
    free list now starts that much further on, so that a later table spans
    its seam and has a joint."""
    n = blocks * eng.block_size
    table, _, _ = eng.pool.allocate(_np.zeros(n, _np.int32), n, n,
                                    share=False)
    eng.pool.release(table)


def check_paged_groups(eng, serve, monkeypatch, group_keys=32, step_groups=2):
    """Run ``serve()`` on ``eng`` — built with the kernels forced
    (``MXNET_FA_DECODE_FORCE_PALLAS=1``) and heads the grouped paged kernel
    takes — with groups of ``group_keys`` keys, ``step_groups`` a kernel
    step (small, so that tiny contexts span several), and hold the engine's
    ``mxtpu_paged_groups_total`` to :func:`paged_groups_by_loops` over the
    tables and write heads of every decode dispatch.  Returns the
    counts."""
    import importlib
    from incubator_mxnet_tpu import telemetry
    fa = importlib.import_module(
        "incubator_mxnet_tpu.kernels.flash_attention")
    monkeypatch.setattr(fa, "_PAGED_GROUP_KEYS", group_keys)
    monkeypatch.setattr(fa, "_PAGED_GQA_STEP_GROUPS", step_groups)
    fa._paged_gqa_pallas.clear_cache()
    calls, count = [], eng._count_decode

    def spy(counts, positions, steps, dispatch_steps):
        calls.append((eng._tables.copy(), _np.array(positions),
                      _np.array(steps)))
        return count(counts, positions, steps, dispatch_steps)

    monkeypatch.setattr(eng, "_count_decode", spy)

    def series():
        values = telemetry.registry.export_state()["counters"].get(
            "mxtpu_paged_groups_total", {}).get("values", {})
        return {f: sum(v for k, v in values.items()
                       if f"model={eng.name}" in k.split(",")
                       and f"fetch={f}" in k.split(","))
                for f in ("run", "blocks")}

    before = series()
    try:
        serve()
    finally:
        fa._paged_gqa_pallas.clear_cache()
    assert eng.program_inventory()["paged_attention"] == "pallas"
    windows = [eng.layout.windows[l] for l in eng.layout.kv_layers]
    want = paged_groups_by_loops(calls, windows, eng.block_size,
                                 eng.num_blocks, group_keys, step_groups)
    got = eng.decode_counters()
    assert {f: got["paged_groups_" + f] for f in want} == want
    after = series()
    assert {f: after[f] - before[f] for f in want} == want
    return want
