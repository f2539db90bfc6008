"""What the served decoders of ``models/`` (``afmoe.py``,
``smallthinker.py``) would each spell out alike, once: RMSNorm and the
rotary embedding as functions, parameters that ADOPT device arrays, and
the two base blocks that carry the serving seam (``docs/serving.md`` "The
layer interface") — ``kv_layout`` / ``serve_embed`` / ``serve_layers`` /
``serve_head`` on the model, ``serve_prefill`` / ``serve_cached`` on a
layer — plus the cacheless forward built from it.

A model file states its parameters' shapes and writes ONE method a layer,
``_block(h, positions, attend, live) -> (h', counts)``: the layer's
equations over jax arrays, with attention handed in.  Every matrix is
stored ``(in, out)`` and applied as ``x @ W``; matrices and activations are
of the model's ``dtype``, products accumulate in float32, norms, rotary
embedding, router scores and softmax are computed in float32.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import NDArray, _invoke

__all__ = ["ServedLayer", "ServedDecoder", "rms_norm", "rotary",
           "causal_conv", "tail_after"]

#: the integer counters an expert layer returns from ``serve_cached``
#: (summed over layers and steps by the engine, added on the host to
#: ``mxtpu_moe_pairs_total`` / ``_pairs_held`` / ``_experts_touched``)
MOE_COUNTERS = ("moe_pairs_total", "moe_pairs_held", "moe_experts_touched")


def rms_norm(x, w, eps):
    """``x * rsqrt(mean(x^2) + eps) * w`` over the last axis, computed in
    float32, returned in ``x``'s type."""
    import jax.numpy as jnp
    from jax import lax
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rotary(x, positions, theta, dims=None):
    """Rotary embedding, half-rotation form, no scaling: ``x`` (B, T, H, D),
    ``positions`` (B, T) int32.  Float32 inside, ``x``'s type out.  With
    ``dims`` only a head's first ``dims`` features are turned (among
    themselves); the rest pass as they are."""
    import jax.numpy as jnp
    if dims is not None and dims != x.shape[-1]:
        return jnp.concatenate(
            [rotary(x[..., :dims], positions, theta), x[..., dims:]], -1)
    D = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=jnp.float32)
                                  / D))                        # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv        # (B, T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :D // 2], xf[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def causal_conv(x, tail, w, bias=None):
    """Depthwise causal convolution (``bias`` (C,) added where given), then
    SiLU: ``x`` (B, T, C) the positions' inputs, ``tail`` (B, K - 1, C) the
    inputs of the K - 1 positions before them, ``w`` (K, C) with ``w[K -
    1]`` on the position itself.  Returns ``(y (B, T, C) in x's type,
    seq)``: ``seq`` (B, T + K - 1, C) float32 is ``tail`` then ``x``, of
    which ``seq[b, n : n + K - 1]`` is the tail after ``n`` positions
    (:func:`tail_after`) — ``tail`` itself, bit for bit, for ``n = 0``."""
    import jax
    import jax.numpy as jnp
    K, T = w.shape[0], x.shape[1]
    seq = jnp.concatenate([tail.astype(jnp.float32),
                           x.astype(jnp.float32)], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(seq[:, j:j + T] * wf[j] for j in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype), seq


def tail_after(seq, n, width):
    """``seq[b, n[b] : n[b] + width]`` for every row: ``seq`` (B, L, C),
    ``n`` (B,) int32 -> (B, width, C)."""
    import jax
    from jax import lax
    return jax.vmap(lambda s, i: lax.dynamic_slice_in_dim(s, i, width, 0))(
        seq, n)


def _adopt(param, value):
    """Make ``value`` (a jax array already on its device, of the
    parameter's shape and type) the parameter's data: no copy, no trip
    through the host, no initial allocation — 8 GB of weights cannot be
    held twice."""
    if tuple(value.shape) != tuple(param.shape):
        raise MXNetError(f"{param.name}: parameter has {param.shape}, "
                         f"given {tuple(value.shape)}")
    if _np.dtype(value.dtype) != _np.dtype(param.dtype):
        raise MXNetError(f"{param.name}: parameter is {param.dtype}, "
                         f"given {value.dtype}")
    param._data = NDArray(value)
    param._deferred_init = None


def _positions(B, T):
    import jax.numpy as jnp
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))


class ServedLayer(HybridBlock):
    """A decoder layer behind the serving seam.  ``shapes`` names its
    parameters (a vector starts as ones unless named in ``random``);
    ``window`` is None where the layer reads every earlier position, else
    the number of latest positions it reads.  The subclass writes
    ``_block``."""

    #: None for a layer that keeps keys and values in the block pool; for a
    #: layer that keeps a state of constant size a sequence instead, that
    #: state's leaves as ``((shape, dtype), ...)`` — the engine holds one
    #: array a leaf, a row a sequence, and hands the rows through
    #: :meth:`serve_recurrent`
    state_shapes = None
    #: None for a layer that keeps K and V of the model's ``kv_heads x
    #: head_dim``; for a latent layer the rows a cached position keeps
    #: INSTEAD, ``((features, dtype), ...)`` — the latent row, then the
    #: index key of a layer that chooses its keys (``KVLayout.rows``) — and
    #: ``select`` how many keys such a layer chooses (``KVLayout.selects``)
    kv_rows = None
    select = None
    #: the softmax scale of the layer's attention where it is not
    #: ``head_dim ** -0.5`` (handed to the attention kernels, not folded
    #: into a weight)
    attn_scale = None
    #: a state layer whose one-token step updates the engine's state
    #: leaves where they lie: the decode programs hand
    #: :meth:`serve_recurrent` each WHOLE leaf (the sequences' rows are its
    #: first B) and take it back whole, where another layer gets its rows
    #: sliced out and written back
    state_in_place = False

    def __init__(self, shapes, dtype, grad_req, window, random=(), **kwargs):
        super().__init__(**kwargs)
        self._names = tuple(shapes)
        self.window = None if window is None else int(window)
        with self.name_scope():
            for name, shape in shapes.items():
                setattr(self, name, self.params.get(
                    name, shape=shape, dtype=dtype, grad_req=grad_req,
                    init="ones" if len(shape) == 1
                    and name not in random else None))

    def _w(self, name):
        return getattr(self, name).data()._data

    def _block(self, h, positions, attend, live):
        """h (B, T, d), positions (B, T), ``attend(q, k, v)`` -> the
        attention of q (B, T, heads, D) over k, v (B, T, kv_heads, D),
        ``live`` (B, T) bool or None.  Returns ``(h', counts)``, counts a
        tuple of int32 scalars in the model's ``serve_counters`` order.

        A layer with ``kv_rows`` is handed ``attend(q_n, q_r, row, w_uk,
        w_uv, scale, index=None)`` instead: queries ``q_n`` (B, T, H, d_n)
        and ``q_r`` (B, T, H, d_r, rotary applied), the position's
        ``row`` (B, T, r + d_r) as the cache is to hold it, the two
        halves of the up-projection ``w_uk`` (r, H, d_n) and ``w_uv`` (r,
        H, d_v), and with ``select`` the index's ``(q_i (B, T, HI, dI),
        w_i (B, T, HI), k_i (B, T, dI))``; it returns the heads' outputs
        (B, T, H, d_v) over the positions the layer may read — the window,
        or the ``select`` positions the index scores highest."""
        raise NotImplementedError

    def serve_prefill(self, h, positions, live=None):
        """A whole prompt with nothing cached: h (B, T, d), positions
        (B, T).  Returns ``(h', k, v)``, k and v (B, T, kv_heads, D) as
        the cache is to hold them — of a layer with ``kv_rows``, ``h'``
        and then those rows, (B, T, features) each."""
        from ..kernels.flash_attention import prefill_attention
        if self.state_shapes is not None:   # from an empty state, not kept
            import jax.numpy as jnp
            rows = tuple(jnp.zeros((h.shape[0],) + tuple(shape), dtype)
                         for shape, dtype in self.state_shapes)
            return self.serve_recurrent(h, positions, rows, live)[0], \
                None, None
        kept = []
        if self.kv_rows is not None:
            h, _ = self._block(h, positions,
                               self._latent_prompt(positions, kept), live)
            return (h,) + tuple(kept)

        def attend(q, k, v):
            kept.extend((k, v))
            return prefill_attention(q, k, v, window=self.window,
                                     scale=self.attn_scale)

        h, _ = self._block(h, positions, attend, live)
        return h, kept[0], kept[1]

    def _latent_prompt(self, positions, kept):
        """The ``attend`` of a latent layer over a whole prompt with
        nothing cached: each sequence's queries over its own rows, in the
        unabsorbed form (``kernels/latent_attention.py``); the rows the
        cache is to hold go to ``kept``, in ``kv_rows``' order."""
        import jax
        from ..kernels.latent_attention import latent_prompt_attention

        def attend(q_n, q_r, row, w_uk, w_uv, scale, index=None):
            kept.append(row)
            if index is not None:
                kept.append(index[2])

            def one(qn, qr, rows, pos, sel):
                return latent_prompt_attention(
                    qn, qr, rows, pos, pos, w_uk, w_uv, scale, self.window,
                    None if sel is None else sel + (self.select,))

            return jax.vmap(one)(q_n, q_r, row, positions, index)
        return attend

    def serve_cached(self, h, positions, attend, live=None):
        """Positions that attend through the cache: ``attend(q, k, v)``
        is the engine's — it writes k and v (B, T, kv_heads, D) where
        ``positions`` say and returns the attention of q (B, T, heads, D)
        over what the cache then holds.  Returns ``(h', counts)``, counts
        a dict of int32 scalars keyed by the model's ``serve_counters``
        (empty for a layer that counts nothing)."""
        h, counts = self._block(h, positions, attend, live)
        return h, dict(zip(MOE_COUNTERS, counts))

    def serve_recurrent(self, h, positions, rows, live=None,
                        snapshot_every=0):
        """Positions of a layer with ``state_shapes``: ``rows`` the
        sequences' state (a tuple of leaves, leading axis B).  Returns
        ``(h', rows', snapshots, counts)`` as ``_block`` describes them."""
        kept = []

        def carry(update):
            out, new, snaps = update(rows, int(snapshot_every))
            kept.extend((new, snaps))
            return out

        h, counts = self._block(h, positions, carry, live)
        return h, kept[0], kept[1], dict(zip(MOE_COUNTERS, counts))

    def hybrid_forward(self, F, x, **params):
        def run(xv):
            return self.serve_prefill(xv, _positions(*xv.shape[:2]))[0]
        return _invoke(run, [x], name=type(self).__name__.lower())


class ServedDecoder(HybridBlock):
    """Embedding -> layers -> final RMSNorm -> untied head without bias.
    ``layers`` is one maker a layer (``make(prefix=...)`` -> a
    :class:`ServedLayer`); ``cfg`` holds what the seam reads:
    ``num_key_value_heads``, ``head_dim``, ``rms_norm_eps`` and the
    parameters' ``dtype``.  Three things a model file may state of itself
    (none is an operator's setting): ``tied_head`` — the head IS the
    embedding's array, one parameter adopted once, and there is no
    ``lm_head``; ``embed_scale`` — the embedding's rows times it;
    ``logit_divisor`` — the logits over it."""

    #: counters the expert layers return from ``serve_cached``
    serve_counters = MOE_COUNTERS

    def __init__(self, vocab_size, hidden_size, max_length, cfg, layers,
                 grad_req, tied_head=False, embed_scale=None,
                 logit_divisor=None, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self._embed_scale, self._logit_divisor = embed_scale, logit_divisor
        self._vocab_size = int(vocab_size)
        self._units = int(hidden_size)
        self._max_length = int(max_length)
        with self.name_scope():
            self.embed_tokens = self.params.get(
                "embed_tokens", shape=(vocab_size, hidden_size),
                dtype=cfg["dtype"], grad_req=grad_req)
            self.layers = []
            for i, make in enumerate(layers):
                layer = make(prefix=f"layers{i}_")
                self.register_child(layer)
                self.layers.append(layer)
            self.norm = self.params.get(
                "norm", shape=(hidden_size,), dtype=cfg["dtype"],
                grad_req=grad_req, init="ones")
            self.lm_head = None if tied_head else self.params.get(
                "lm_head", shape=(hidden_size, vocab_size),
                dtype=cfg["dtype"], grad_req=grad_req)

    # -- weights ----------------------------------------------------------
    def adopt_arrays(self, tree):
        """Take device arrays as the parameters, without a copy:
        ``tree = {"embed_tokens", "norm", "lm_head", "layers": [{name:
        array}]}`` with a layer's names as it registers them (the
        model's reference under ``benchmark/chip/reference/`` makes
        exactly this; no ``"lm_head"`` for a tied head)."""
        if (self.lm_head is None) != ("lm_head" not in tree):
            raise MXNetError(
                "a tied head is the embedding's array: the tree holds "
                "\"lm_head\" exactly where the model has one")
        _adopt(self.embed_tokens, tree["embed_tokens"])
        _adopt(self.norm, tree["norm"])
        if self.lm_head is not None:
            _adopt(self.lm_head, tree["lm_head"])
        if len(tree["layers"]) != len(self.layers):
            raise MXNetError(f"{len(tree['layers'])} layers given, the "
                             f"model has {len(self.layers)}")
        for layer, arrays in zip(self.layers, tree["layers"]):
            if set(arrays) != set(layer._names):
                raise MXNetError(
                    f"{layer.name}: given {sorted(arrays)}, the layer has "
                    f"{sorted(layer._names)}")
            for name in layer._names:
                _adopt(getattr(layer, name), arrays[name])

    # -- the serving seam -------------------------------------------------
    def kv_layout(self):
        c = self._cfg
        return {"num_layers": len(self.layers),
                "kv_heads": c["num_key_value_heads"],
                "head_dim": c["head_dim"], "dtype": str(c["dtype"]),
                "windows": tuple(l.window for l in self.layers),
                "states": tuple(l.state_shapes for l in self.layers),
                "rows": tuple(l.kv_rows for l in self.layers),
                "selects": tuple(l.select for l in self.layers),
                "max_length": self._max_length}

    def serve_layers(self):
        return list(self.layers)

    def serve_embed(self, tokens, positions):
        """tokens, positions (B, T) int32 -> h (B, T, d).  Positions are
        the layers' business (rotary, where a layer carries it)."""
        del positions
        h = self.embed_tokens.data()._data[tokens]
        if self._embed_scale is None:
            return h
        import jax.numpy as jnp
        return h * jnp.asarray(self._embed_scale, h.dtype)

    def _head_logits(self, x):
        """x (B, T, d) normed -> float32 logits: the head's product (with
        a tied head the embedding's rows, contracted where they lie), over
        the model's divisor."""
        import jax.numpy as jnp
        from jax import lax
        if self.lm_head is None:
            logits = lax.dot_general(
                x, self.embed_tokens.data()._data,
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            logits = jnp.dot(x, self.lm_head.data()._data,
                             preferred_element_type=jnp.float32)
        if self._logit_divisor is None:
            return logits
        return logits / self._logit_divisor

    def serve_head(self, h):
        """h (B, T, d) -> float32 logits (B, T, vocab)."""
        return self._head_logits(
            rms_norm(h, self.norm.data()._data, self._cfg["rms_norm_eps"]))

    def hybrid_forward(self, F, ids, **params):
        """Full causal forward, no cache: ids (B, T) -> logits (B, T, V)."""
        def run(iv):
            pos = _positions(*iv.shape)
            h = self.serve_embed(iv, pos)
            for layer in self.layers:
                h = layer.serve_prefill(h, pos)[0]
            return self.serve_head(h)
        return _invoke(run, [ids], name=type(self).__name__.lower(),
                       differentiable=False)
