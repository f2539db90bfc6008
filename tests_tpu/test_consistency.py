"""CPU-vs-TPU numerical consistency over the op corpus (reference:
test_utils.check_consistency as used by tests/python/gpu/
test_operator_gpu.py — the cross-device tier).  50+ ops, forward AND
backward compared between the jax CPU backend and the live chip."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import test_utils as tu

nd = mx.nd


pytestmark = pytest.mark.usefixtures("highest_matmul_precision")


def _u(lo, hi, shape=(3, 4), seed=0):
    rng = onp.random.default_rng(seed)
    return (rng.random(shape) * (hi - lo) + lo).astype(onp.float32)


_CTXS = None


def _ctx_list():
    global _CTXS
    if _CTXS is None:
        _CTXS = [mx.cpu(0), mx.tpu(0)]
    return _CTXS


# elementwise / unary — the chip's transcendentals (log, tanh, gammaln,
# power, ...) are approximations good to a few 1e-4 relative (measured on
# the v5e: up to 6.5e-4), so the tier allows 1e-3
UNARY = [
    ("abs", (-2, 2)), ("negative", (-2, 2)), ("reciprocal", (0.5, 2.0)),
    ("square", (-2, 2)), ("sqrt", (0.2, 3.0)), ("rsqrt", (0.3, 3.0)),
    ("cbrt", (0.2, 3.0)), ("exp", (-1, 1)), ("expm1", (-1, 1)),
    ("log", (0.2, 3.0)), ("log1p", (-0.5, 2.0)), ("log2", (0.2, 3.0)),
    ("log10", (0.2, 3.0)), ("sin", (-2, 2)), ("cos", (-2, 2)),
    ("tan", (-1, 1)), ("arcsin", (-0.8, 0.8)), ("arccos", (-0.8, 0.8)),
    ("arctan", (-2, 2)), ("sinh", (-1.5, 1.5)), ("cosh", (-1.5, 1.5)),
    ("tanh", (-1.5, 1.5)), ("arcsinh", (-2, 2)), ("arctanh", (-0.7, 0.7)),
    ("sigmoid", (-2, 2)), ("relu", (-2, 2)), ("gelu", (-2, 2)),
    ("softsign", (-2, 2)), ("erf", (-1.5, 1.5)), ("gammaln", (0.5, 3.0)),
    ("floor", (-2, 2)), ("ceil", (-2, 2)), ("round", (-2, 2)),
    ("sign", (-2, 2)), ("square", (-3, 3)),
]


@pytest.mark.parametrize("name,domain", UNARY,
                         ids=[f"{u[0]}_{i}" for i, u in enumerate(UNARY)])
def test_unary_consistency(name, domain):
    fn = getattr(nd, name)
    grad = name not in ("floor", "ceil", "round", "sign")
    tu.check_consistency(lambda x: fn(x), [_u(*domain, seed=2)],
                         ctx_list=_ctx_list(), grad=grad,
                         rtol=1e-3, atol=1e-5)


BINARY = ["add", "subtract", "multiply", "divide", "maximum", "minimum",
          "broadcast_add", "broadcast_mul", "broadcast_div", "hypot",
          "power"]


@pytest.mark.parametrize("name", BINARY)
def test_binary_consistency(name):
    fn = getattr(nd, name)
    tu.check_consistency(lambda a, b: fn(a, b),
                         [_u(0.5, 2.0, seed=3), _u(0.5, 2.0, seed=4)],
                         ctx_list=_ctx_list(), rtol=1e-3, atol=1e-5)


REDUCTIONS = ["sum", "mean", "max", "min", "prod", "norm",
              "nansum", "argmax", "argmin"]


@pytest.mark.parametrize("name", REDUCTIONS)
def test_reduction_consistency(name):
    fn = getattr(nd, name)
    grad = name not in ("argmax", "argmin")
    tu.check_consistency(lambda x: fn(x), [_u(0.2, 2.0, (4, 5), seed=5)],
                         ctx_list=_ctx_list(), grad=grad,
                         rtol=1e-4, atol=1e-4)


# MXU-path ops: the TPU may accumulate differently — looser tolerance
def test_dot_consistency():
    tu.check_consistency(
        lambda a, b: nd.dot(a, b),
        [_u(-1, 1, (8, 16), seed=6), _u(-1, 1, (16, 4), seed=7)],
        ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)


def test_fully_connected_consistency():
    tu.check_consistency(
        lambda x, w, b: nd.FullyConnected(x, w, b, num_hidden=8),
        [_u(-1, 1, (4, 16), seed=8), _u(-0.2, 0.2, (8, 16), seed=9),
         _u(-0.1, 0.1, (8,), seed=10)],
        ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)


def test_convolution_consistency():
    tu.check_consistency(
        lambda x, w: mx.nd.Convolution(x, w, kernel=(3, 3), pad=(1, 1),
                                       num_filter=4, no_bias=True),
        [_u(-1, 1, (2, 3, 8, 8), seed=11),
         _u(-0.3, 0.3, (4, 3, 3, 3), seed=12)],
        ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)


def test_softmax_family_consistency():
    for fn in (nd.softmax, nd.log_softmax):
        tu.check_consistency(lambda x, f=fn: f(x),
                             [_u(-3, 3, (4, 7), seed=13)],
                             ctx_list=_ctx_list(), rtol=1e-3, atol=1e-4)


def test_batchnorm_consistency():
    tu.check_consistency(
        lambda x, g, b: mx.nd.BatchNorm(
            x, g, b, mx.nd.zeros((3,)), mx.nd.ones((3,)),
            fix_gamma=False),
        [_u(-1, 1, (4, 3, 5, 5), seed=14), _u(0.5, 1.5, (3,), seed=15),
         _u(-0.2, 0.2, (3,), seed=16)],
        ctx_list=_ctx_list(), rtol=1e-3, atol=1e-3)


def test_layernorm_consistency():
    tu.check_consistency(
        lambda x, g, b: mx.nd.LayerNorm(x, g, b),
        [_u(-1, 1, (4, 8), seed=17), _u(0.5, 1.5, (8,), seed=18),
         _u(-0.2, 0.2, (8,), seed=19)],
        ctx_list=_ctx_list(), rtol=1e-3, atol=1e-3)


def test_take_embedding_consistency():
    x = _u(-1, 1, (10, 4), seed=20)
    idx = onp.array([1, 3, 7], onp.float32)

    def emb(w):
        # indices on the weight's device: the tier's default context is
        # tpu(0), and this body also runs for the cpu(0) reference
        return mx.nd.Embedding(
            mx.nd.array(idx, ctx=w.context, dtype=onp.int32), w,
            input_dim=10, output_dim=4)
    tu.check_consistency(emb, [x], ctx_list=_ctx_list(),
                         rtol=1e-5, atol=1e-6)


def test_train_step_consistency():
    """A whole LeNet-ish training step must match CPU within tolerance —
    the end-to-end version of the per-op checks."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    X = _u(-1, 1, (8, 1, 12, 12), seed=21)
    Y = onp.arange(8, dtype=onp.float32) % 4
    weights = {}
    for ctx in _ctx_list():
        with ctx:
            mx.random.seed(7)
            net = nn.HybridSequential()
            net.add(nn.Conv2D(4, kernel_size=3, activation="relu"),
                    nn.Flatten(), nn.Dense(4))
            net.initialize(init=mx.init.Xavier())
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
            loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
            for _ in range(3):
                with mx.autograd.record():
                    loss = loss_fn(net(mx.nd.array(X)),
                                   mx.nd.array(Y)).mean()
                loss.backward()
                tr.step(8)
            # by position: the second build's auto-prefixes count on
            # (conv2d0_weight vs conv2d1_weight)
            weights[str(ctx)] = [
                (k, p.data().asnumpy())
                for k, p in net.collect_params().items()]
    (k0, w0), (k1, w1) = weights.items()
    for (n0, a0), (n1, a1) in zip(w0, w1):
        tu.assert_almost_equal(a0, a1, rtol=2e-2, atol=1e-3,
                               names=(f"{n0}@{k0}", f"{n1}@{k1}"))


# ---------------------------------------------------------------------------
# round-3 op-corpus extensions (linalg / spatial / misc) on the chip
# ---------------------------------------------------------------------------
def test_linalg_family_consistency():
    rng = onp.random.default_rng(30)
    a = rng.standard_normal((4, 4)).astype(onp.float32)
    spd = a @ a.T + 4 * onp.eye(4, dtype=onp.float32)
    b = rng.standard_normal((4, 3)).astype(onp.float32)
    c = onp.zeros((4, 3), onp.float32)
    # matmul-family ops ride the MXU: same loosened tolerance as
    # test_dot_consistency (default TPU matmul precision rounds
    # operands to bf16)
    tu.check_consistency(
        lambda x, y, z: nd.linalg_gemm(x, y, z, alpha=1.5),
        [a, b, c], ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)
    tu.check_consistency(lambda x: nd.linalg_potrf(x), [spd],
                         ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)
    tu.check_consistency(lambda x: nd.linalg_syrk(x), [a],
                         ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)
    tu.check_consistency(lambda x: nd.linalg_inverse(x), [spd],
                         ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)


def test_spatial_ops_consistency():
    rng = onp.random.default_rng(31)
    x = rng.standard_normal((1, 2, 6, 6)).astype(onp.float32)
    theta = onp.array([1, 0, 0.1, 0, 1, -0.1], onp.float32).reshape(1, 6)
    # einsum inside GridGenerator / DeformableConvolution rides the MXU:
    # loosened tolerance like the other matmul-path checks
    tu.check_consistency(
        lambda d, t: nd.SpatialTransformer(d, t, target_shape=(6, 6)),
        [x, theta], ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)
    tu.check_consistency(lambda d: nd.LRN(d, nsize=3), [x],
                         ctx_list=_ctx_list(), rtol=1e-4, atol=1e-5)
    off = onp.zeros((1, 2 * 9, 6, 6), onp.float32)
    w = rng.standard_normal((3, 2, 3, 3)).astype(onp.float32)
    tu.check_consistency(
        lambda d, o, wt: nd.DeformableConvolution(d, o, wt,
                                                  kernel=(3, 3),
                                                  pad=(1, 1)),
        [x, off, w], ctx_list=_ctx_list(), rtol=2e-2, atol=1e-3)


def test_misc_ext_consistency():
    rng = onp.random.default_rng(32)
    x = rng.standard_normal((2, 8, 4, 4)).astype(onp.float32)
    tu.check_consistency(lambda d: nd.depth_to_space(d, 2), [x],
                         ctx_list=_ctx_list(), rtol=1e-6, atol=1e-6)
    flat = rng.standard_normal((3, 8)).astype(onp.float32)
    tu.check_consistency(lambda d: nd.logsumexp(d, axis=1), [flat],
                         ctx_list=_ctx_list(), rtol=1e-5, atol=1e-5)
    tu.check_consistency(lambda d: nd.ifft(nd.fft(d)), [flat],
                         ctx_list=_ctx_list(), rtol=1e-3, atol=1e-3)
    # moments returns a pair; compare via concat
    tu.check_consistency(
        lambda d: nd.concat(*nd.moments(d, axes=1), dim=0), [flat],
        ctx_list=_ctx_list(), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# round-4 chip coverage: int8 path, masked Pallas flash attention, and
# the legacy-tail ops (VERDICT r04 next #4: "extend the consistency list
# with the families that have TPU-risky numerics")
# ---------------------------------------------------------------------------
def test_int8_quantized_dense_consistency():
    """The int8 inference path (scale calc, int8 matmul with int32
    accumulate, dequantize) must agree CPU vs chip — the TPU lowers the
    int8 dot very differently from the CPU backend."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.contrib import quantization as q
    from incubator_mxnet_tpu.gluon import nn
    rng = onp.random.default_rng(40)
    X = rng.standard_normal((8, 16)).astype(onp.float32)
    outs = []
    for ctx in _ctx_list():
        with ctx:
            mx.random.seed(11)
            net = nn.HybridSequential()
            net.add(nn.Dense(6, in_units=16))
            net.initialize(init=mx.init.Xavier())
            calib = [mx.nd.array(X)]
            qnet = q.quantize_net(net, calib_data=calib,
                                  calib_mode="naive")
            outs.append((str(ctx), qnet(mx.nd.array(X)).asnumpy()))
    (k0, o0), (k1, o1) = outs
    tu.assert_almost_equal(o0, o1, rtol=2e-2, atol=2e-3,
                           names=(k0, k1))


def test_flash_attention_kernel_consistency():
    """The Pallas kernel runs in interpret mode on CPU and as a real
    Mosaic kernel on the chip: dense, causal, and MASKED (additive-bias)
    variants must agree — this is the on-chip proof of the round-4
    masked path."""
    from incubator_mxnet_tpu.kernels import flash_attention
    rng = onp.random.default_rng(41)
    B, H, T, D = 2, 2, 128, 64
    q_ = rng.standard_normal((B, H, T, D)).astype(onp.float32)
    k_ = rng.standard_normal((B, H, T, D)).astype(onp.float32)
    v_ = rng.standard_normal((B, H, T, D)).astype(onp.float32)
    mask = onp.zeros((B, T), onp.int32)
    mask[0, :77] = 1
    mask[1, :] = 1
    for kwargs in ({}, {"causal": True}, {"mask": mask}):
        outs = []
        for ctx in _ctx_list():
            with ctx:
                kw = dict(kwargs)
                if "mask" in kw:
                    kw["mask"] = mx.nd.array(mask, dtype="int32")._data
                out = flash_attention(
                    mx.nd.array(q_)._data, mx.nd.array(k_)._data,
                    mx.nd.array(v_)._data, **kw)
                outs.append((str(ctx), onp.asarray(out)))
        (k0, o0), (k1, o1) = outs
        # a (B, Tk) KEY mask leaves every query row well-defined (each
        # attends only the valid keys), so ALL rows are compared —
        # including the tile past the mask boundary, where a Mosaic
        # block-boundary bug would hide
        tu.assert_almost_equal(o0, o1, rtol=2e-2, atol=2e-3,
                               names=(f"{kwargs}@{k0}",
                                      f"{kwargs}@{k1}"))


def test_legacy_tail_consistency():
    rng = onp.random.default_rng(42)
    x = rng.standard_normal((2, 8, 6, 6)).astype(onp.float32)
    rois = onp.array([[0, 0, 0, 4, 4], [1, 1, 1, 5, 5]], onp.float32)
    tu.check_consistency(
        lambda d, r: nd.contrib.PSROIPooling(
            d, r, spatial_scale=1.0, output_dim=2, pooled_size=2),
        [x, rois], ctx_list=_ctx_list(), rtol=1e-4, atol=1e-5)
    feat = rng.standard_normal((3, 10)).astype(onp.float32)
    h = rng.integers(0, 6, (1, 10)).astype(onp.int32)
    s = rng.choice([-1.0, 1.0], (1, 10)).astype(onp.float32)

    def sketch(d):
        # aux tensors must live where check_consistency put the data —
        # the fixture's default ctx is tpu(0), which would mix devices
        # on the cpu pass
        return nd.contrib.count_sketch(
            d, mx.nd.array(h, dtype="int32", ctx=d.context),
            mx.nd.array(s, ctx=d.context), out_dim=6)
    tu.check_consistency(sketch, [feat], ctx_list=_ctx_list(),
                         rtol=1e-5, atol=1e-5)
    img = rng.standard_normal((1, 2, 5, 7)).astype(onp.float32)
    tu.check_consistency(
        lambda d: nd.contrib.BilinearResize2D(d, mode="to_even_up"),
        [img], ctx_list=_ctx_list(), rtol=1e-4, atol=1e-5)
    scores = rng.standard_normal((4, 5)).astype(onp.float32)
    labels = onp.array([0, 2, 4, 1], onp.float32)
    tu.check_consistency(
        lambda d: mx.nd.SVMOutput(
            d, mx.nd.array(labels, ctx=d.context)), [scores],
        ctx_list=_ctx_list(), rtol=1e-5, atol=1e-6)


def test_gpt_generate_consistency():
    """The LM forward logits must agree CPU vs chip (MXU-tolerance like
    every matmul test here — bf16 operand rounding forbids exact token
    claims), and the KV-cached lax.scan generator must RUN on the chip:
    right shape, prompt preserved, tokens in-vocab.  Token-exact
    equality across backends is not asserted: one near-tie argmax under
    bf16 matmul rounding would legitimately diverge."""
    from incubator_mxnet_tpu.models import gpt
    rng = onp.random.default_rng(43)
    prompt = rng.integers(1, 60, (2, 5)).astype(onp.int32)
    logits, toks = [], []
    for ctx in _ctx_list():
        with ctx:
            mx.random.seed(21)
            net = gpt.gpt_tiny(vocab_size=60, dropout=0.0)
            net.initialize(init=mx.init.Normal(0.02))
            logits.append(net(mx.nd.array(prompt,
                                          dtype="int32")).asnumpy())
            out = net.generate(mx.nd.array(prompt, dtype="int32"),
                               max_new_tokens=8, temperature=0.0,
                               use_cache=True)
            toks.append(out.asnumpy())
    tu.assert_almost_equal(logits[0], logits[1], rtol=2e-2, atol=2e-3,
                           names=("logits@cpu", "logits@accel"))
    for t in toks:
        assert t.shape == (2, 13)
        onp.testing.assert_array_equal(t[:, :5], prompt)
        assert ((t >= 0) & (t < 60)).all()


def test_flash_lse_and_backward_consistency():
    """Round-5 chip proof: the with-lse kernel variant (out AND
    logsumexp) and its Pallas BACKWARD (incl. the lse cotangent that
    blockwise ring attention exercises) agree CPU-interpret vs the real
    Mosaic kernels."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.kernels import flash_attention_lse
    rng = onp.random.default_rng(51)
    B, H, T, D = 2, 2, 128, 64
    q_ = rng.standard_normal((B, H, T, D)).astype(onp.float32)
    k_ = rng.standard_normal((B, H, T, D)).astype(onp.float32)
    v_ = rng.standard_normal((B, H, T, D)).astype(onp.float32)

    def run(ctx, causal):
        with ctx:
            qj = mx.nd.array(q_)._data
            kj = mx.nd.array(k_)._data
            vj = mx.nd.array(v_)._data

            def loss(q, k, v):
                o, lse = flash_attention_lse(q, k, v, causal=causal)
                return ((o.astype(jnp.float32) ** 2).sum()
                        + (1.3 * lse).sum())

            val, grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2))(qj, kj, vj)
            return float(val), [onp.asarray(g) for g in grads]

    for causal in (False, True):
        (v0, g0), (v1, g1) = (run(c, causal) for c in _ctx_list())
        assert abs(v0 - v1) <= 2e-2 * max(1.0, abs(v0)), (causal, v0, v1)
        for a, b, nm in zip(g0, g1, "qkv"):
            tu.assert_almost_equal(a, b, rtol=2e-2, atol=2e-3,
                                   names=(f"cpu d{nm}", f"tpu d{nm}"))


def test_np_fft_consistency():
    """np.fft round-5 namespace: XLA's CPU (Ducc) and TPU FFT
    implementations must agree on values, not just shapes."""
    rng = onp.random.default_rng(52)
    x = rng.standard_normal((4, 64)).astype(onp.float32)
    outs = {}
    for ctx in _ctx_list():
        with ctx:
            a = mx.np.array(x)
            outs[str(ctx)] = {
                "fft": mx.np.fft.fft(a).asnumpy(),
                "rfft": mx.np.fft.rfft(a).asnumpy(),
                "irfft": mx.np.fft.irfft(mx.np.fft.rfft(a)).asnumpy(),
                "fft2": mx.np.fft.fft2(a).asnumpy(),
            }
    (k0, o0), (k1, o1) = outs.items()
    for name in o0:
        tu.assert_almost_equal(onp.abs(o0[name]), onp.abs(o1[name]),
                               rtol=2e-3, atol=2e-3,
                               names=(f"{name}@{k0}", f"{name}@{k1}"))
