"""Testing utilities (reference: python/mxnet/test_utils.py — the
load-bearing fixture module of the reference suite, SURVEY §4):
finite-difference gradient checking, dtype-aware comparisons,
cross-context consistency, random array factories.

Works on both Symbols (bound through the executor) and plain callables
over NDArrays — the TPU build's ops are jax-lowered either way.
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError
from .context import Context, cpu, current_context
from . import ndarray as nd
from .ndarray.ndarray import NDArray

__all__ = [
    "default_context", "set_default_context", "default_rtol_atol",
    "same", "almost_equal", "assert_almost_equal",
    "rand_ndarray", "rand_shape_2d", "rand_shape_3d", "rand_shape_nd",
    "check_numeric_gradient", "check_symbolic_forward",
    "check_symbolic_backward", "check_consistency", "simple_forward",
]


_default_ctx: Context | None = None

# dtype-aware tolerance table (reference: test_utils default_numeric_eps /
# assert_almost_equal defaults, widened for bf16)
_RTOL = {_np.dtype(_np.float16): 1e-2, _np.dtype(_np.float32): 1e-4,
         _np.dtype(_np.float64): 1e-6}
_ATOL = {_np.dtype(_np.float16): 1e-2, _np.dtype(_np.float32): 1e-5,
         _np.dtype(_np.float64): 1e-8}


def default_context() -> Context:
    """The context tests run on (reference: test_utils.default_context).
    Override with set_default_context — the GPU/TPU-tier trick of
    re-running one suite on another device."""
    return _default_ctx if _default_ctx is not None else current_context()


def set_default_context(ctx: Context):
    global _default_ctx
    _default_ctx = ctx


def default_rtol_atol(dtype):
    d = _np.dtype(dtype)
    try:
        import ml_dtypes
        if d == _np.dtype(ml_dtypes.bfloat16):
            return 1e-2, 1e-2
    except ImportError:
        pass
    return _RTOL.get(d, 1e-5), _ATOL.get(d, 1e-7)


def _as_np(a):
    if isinstance(a, NDArray):
        return a.asnumpy()
    return _np.asarray(a)


def same(a, b) -> bool:
    """Exact equality (reference: test_utils.same)."""
    return _np.array_equal(_as_np(a), _as_np(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False) -> bool:
    a, b = _as_np(a), _as_np(b)
    if rtol is None or atol is None:
        r, t = default_rtol_atol(a.dtype)
        rtol = rtol if rtol is not None else r
        atol = atol if atol is not None else t
    return _np.allclose(a.astype(_np.float64), b.astype(_np.float64),
                        rtol=rtol, atol=atol, equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Dtype-aware allclose with a useful failure message (reference:
    test_utils.assert_almost_equal)."""
    an, bn = _as_np(a), _as_np(b)
    if rtol is None or atol is None:
        r, t = default_rtol_atol(an.dtype)
        rtol = rtol if rtol is not None else r
        atol = atol if atol is not None else t
    if _np.allclose(an.astype(_np.float64), bn.astype(_np.float64),
                    rtol=rtol, atol=atol, equal_nan=equal_nan):
        return
    af, bf = an.astype(_np.float64), bn.astype(_np.float64)
    err = _np.abs(af - bf)
    denom = _np.maximum(_np.abs(bf), atol / max(rtol, 1e-300))
    rel = err / _np.maximum(denom, 1e-300)
    idx = _np.unravel_index(_np.argmax(rel), rel.shape) if rel.size \
        else ()
    raise AssertionError(
        f"{names[0]} and {names[1]} differ beyond rtol={rtol} atol={atol}"
        f": max abs err {err.max() if err.size else 0:.3e}, max rel err "
        f"{rel.max() if rel.size else 0:.3e} at {idx}; "
        f"{names[0]}[{idx}]={af[idx] if err.size else None} "
        f"{names[1]}[{idx}]={bf[idx] if err.size else None}")


# ---------------------------------------------------------------------------
# random data factories (reference: test_utils.rand_ndarray/rand_shape_*)
# ---------------------------------------------------------------------------
def rand_shape_2d(dim0=10, dim1=10):
    return (_np.random.randint(1, dim0 + 1),
            _np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (_np.random.randint(1, dim0 + 1),
            _np.random.randint(1, dim1 + 1),
            _np.random.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10):
    return tuple(_np.random.randint(1, dim + 1, size=num_dim))


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 ctx=None, scale=1.0):
    """Random array, dense or sparse storage (reference:
    test_utils.rand_ndarray)."""
    dtype = dtype or _np.float32
    data = (_np.random.standard_normal(shape) * scale).astype(dtype)
    if stype == "default":
        return nd.array(data, ctx=ctx, dtype=dtype)
    density = 0.1 if density is None else density
    mask = _np.random.random(shape) < density
    data = _np.where(mask, data, 0).astype(dtype)
    from .ndarray import sparse as _sp
    if stype == "row_sparse":
        return _sp.RowSparseNDArray.from_dense(nd.array(data, dtype=dtype))
    if stype == "csr":
        return _sp.CSRNDArray.from_dense(nd.array(data, dtype=dtype))
    raise MXNetError(f"unknown stype {stype!r}")


# ---------------------------------------------------------------------------
# gradient checking (reference: test_utils.check_numeric_gradient)
# ---------------------------------------------------------------------------
def _normalize_fn(fn_or_sym, location):
    """Return (callable(np arrays)->list[np], input names).  Symbols are
    evaluated through eval_graph; callables take NDArrays positionally."""
    from .symbol.symbol import Symbol, eval_graph
    if isinstance(fn_or_sym, Symbol):
        names = fn_or_sym.list_arguments()
        if isinstance(location, dict):
            order = [n for n in names if n in location]
        else:
            order = names[:len(location)]

        def run(*arrays):
            vals = {n: a for n, a in zip(order, arrays)}
            outs = eval_graph(fn_or_sym, vals, is_train=True)
            return outs if isinstance(outs, list) else [outs]
        return run, order

    def run(*arrays):
        outs = fn_or_sym(*arrays)
        if isinstance(outs, (list, tuple)):
            return list(outs)
        return [outs]
    names = [f"arg{i}" for i in range(len(location))]
    return run, names


def _loc_list(location):
    if isinstance(location, dict):
        return [_np.asarray(_as_np(v), _np.float64)
                for v in location.values()]
    return [_np.asarray(_as_np(v), _np.float64) for v in location]


def check_numeric_gradient(fn_or_sym, location, aux_states=None,
                           numeric_eps=1e-3, rtol=1e-2, atol=None,
                           grad_nodes=None, dtype=_np.float64, seed=0):
    """Central-difference gradient check against autograd (reference:
    test_utils.check_numeric_gradient — the universal grad test).

    The objective is ``sum(out * proj)`` for a fixed random projection, so
    one scalar objective checks the whole Jacobian action.
    """
    from . import autograd as _ag
    run, names = _normalize_fn(fn_or_sym, location)
    locs64 = _loc_list(location)
    comp_dtype = _np.float32 if dtype == _np.float32 else _np.float64
    rng = _np.random.default_rng(seed)

    # fixed projections, one per output
    probe_out = run(*[nd.array(l.astype(comp_dtype)) for l in locs64])
    projs = [rng.standard_normal(_as_np(o).shape) for o in probe_out]

    def objective_np(arrays_np):
        outs = run(*[nd.array(a.astype(comp_dtype)) for a in arrays_np])
        total = 0.0
        for o, p in zip(outs, projs):
            total += float((_as_np(o).astype(_np.float64) * p).sum())
        return total

    grad_idx = (list(range(len(locs64))) if grad_nodes is None
                else list(grad_nodes))

    # analytic grads via the tape
    inputs = [nd.array(l.astype(comp_dtype)) for l in locs64]
    for i in grad_idx:
        inputs[i].attach_grad()
    with _ag.record():
        outs = run(*inputs)
        loss = None
        for o, p in zip(outs, projs):
            term = (o * nd.array(p.astype(comp_dtype))).sum()
            loss = term if loss is None else loss + term
    loss.backward()
    analytic = {i: inputs[i].grad.asnumpy().astype(_np.float64)
                for i in grad_idx}

    # numeric central differences
    for i in grad_idx:
        base = [l.copy() for l in locs64]
        num = _np.zeros_like(base[i])
        flat = base[i].reshape(-1)
        nflat = num.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + numeric_eps
            fp = objective_np(base)
            flat[j] = orig - numeric_eps
            fm = objective_np(base)
            flat[j] = orig
            nflat[j] = (fp - fm) / (2 * numeric_eps)
        a = analytic[i]
        atol_i = atol if atol is not None else 1e-4 + 1e-2 * _np.abs(
            num).max()
        assert_almost_equal(
            num, a, rtol=rtol, atol=atol_i,
            names=(f"numeric_grad({names[i]})",
                   f"autograd_grad({names[i]})"))


def check_symbolic_forward(fn_or_sym, location, expected, rtol=1e-4,
                           atol=1e-6, aux_states=None):
    """Forward vs expected numpy values (reference:
    test_utils.check_symbolic_forward)."""
    run, _ = _normalize_fn(fn_or_sym, location)
    outs = run(*[nd.array(l) for l in _loc_list(location)])
    expected = expected if isinstance(expected, (list, tuple)) \
        else [expected]
    for o, e in zip(outs, expected):
        assert_almost_equal(_as_np(o), _np.asarray(e), rtol=rtol,
                            atol=atol, names=("forward", "expected"))


def check_symbolic_backward(fn_or_sym, location, out_grads, expected,
                            rtol=1e-4, atol=1e-6, grad_nodes=None):
    """Backward vs expected grads (reference:
    test_utils.check_symbolic_backward)."""
    from . import autograd as _ag
    run, _ = _normalize_fn(fn_or_sym, location)
    locs = _loc_list(location)
    inputs = [nd.array(l.astype(_np.float32)) for l in locs]
    grad_idx = (list(range(len(inputs))) if grad_nodes is None
                else list(grad_nodes))
    for i in grad_idx:
        inputs[i].attach_grad()
    with _ag.record():
        outs = run(*inputs)
        og = out_grads if isinstance(out_grads, (list, tuple)) \
            else [out_grads]
        loss = None
        for o, g in zip(outs, og):
            term = (o * nd.array(_as_np(g).astype(_np.float32))).sum()
            loss = term if loss is None else loss + term
    loss.backward()
    expected = expected if isinstance(expected, (list, tuple)) \
        else [expected]
    for i, e in zip(grad_idx, expected):
        assert_almost_equal(inputs[i].grad.asnumpy(), _np.asarray(e),
                            rtol=rtol, atol=atol,
                            names=(f"grad({i})", "expected"))


def check_consistency(fn_or_sym, location, ctx_list=None, rtol=None,
                      atol=None, grad=True):
    """Run the same computation on several contexts and require matching
    outputs (and grads) (reference: test_utils.check_consistency — the
    CPU-vs-GPU tier; here CPU-jax vs TPU-jax)."""
    from . import autograd as _ag
    if ctx_list is None:
        ctx_list = [cpu(0)]
    results = []
    for ctx in ctx_list:
        run, _ = _normalize_fn(fn_or_sym, location)
        inputs = [nd.array(l.astype(_np.float32), ctx=ctx)
                  for l in _loc_list(location)]
        if grad:
            for p in inputs:
                p.attach_grad()
            with _ag.record():
                outs = run(*inputs)
                loss = None
                for o in outs:
                    term = o.sum()
                    loss = term if loss is None else loss + term
            loss.backward()
            grads = [p.grad.asnumpy() for p in inputs]
        else:
            outs = run(*inputs)
            grads = []
        results.append(([_as_np(o) for o in outs], grads))
    ref_outs, ref_grads = results[0]
    for (outs, grads), ctx in list(zip(results, ctx_list))[1:]:
        for o, r in zip(outs, ref_outs):
            assert_almost_equal(o, r, rtol=rtol, atol=atol,
                                names=(f"out@{ctx}",
                                       f"out@{ctx_list[0]}"))
        for g, r in zip(grads, ref_grads):
            assert_almost_equal(g, r, rtol=rtol, atol=atol,
                                names=(f"grad@{ctx}",
                                       f"grad@{ctx_list[0]}"))
    return results


def simple_forward(fn_or_sym, ctx=None, is_train=False, **inputs):
    """One-shot forward with kwargs inputs (reference:
    test_utils.simple_forward)."""
    run, names = _normalize_fn(fn_or_sym, inputs)
    outs = run(*[nd.array(_as_np(v)) for v in inputs.values()])
    return outs[0] if len(outs) == 1 else outs
