"""Shared test decorators (reference: tests/python/unittest/common.py —
@with_seed seeded-retry pattern for stochastic ops)."""
import functools
import logging

import numpy as _np


def with_seed(seed=None, retries=2):
    """Seed numpy+mx per call; on failure retry with a fresh seed and LOG
    the failing seed so the run is reproducible (reference: common.py
    with_seed)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import incubator_mxnet_tpu as mx
            attempts = 1 if seed is not None else retries
            last = None
            for i in range(attempts):
                s = seed if seed is not None else _np.random.randint(2**31)
                _np.random.seed(s)
                mx.random.seed(s)
                try:
                    return fn(*args, **kwargs)
                except AssertionError as e:
                    last = e
                    logging.error("%s failed with seed %d (attempt %d)",
                                  fn.__name__, s, i + 1)
            raise last
        return wrapper
    return deco


def greedy_reference(net, prompt, max_new_tokens):
    """The cache-free oracle of the serving tests: the greedy
    continuation of ``prompt`` by ``net.generate(use_cache=False)``,
    which re-runs the whole prefix at every step — no KV cache, no
    engine, none of the serving programs."""
    import incubator_mxnet_tpu as mx
    ids = mx.nd.array(_np.asarray([prompt], _np.int32))
    out = net.generate(ids, max_new_tokens=int(max_new_tokens),
                       use_cache=False, temperature=0.0)
    return [int(t) for t in
            _np.asarray(out.asnumpy()).reshape(-1)[len(prompt):]]
