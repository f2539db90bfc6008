"""BERT (reference workload: GluonNLP scripts/bert — the judged BASELINE
metric is BERT-large pretraining samples/sec/chip; the reference repo itself
provides the ops BERT is built from: gluon.nn.Dense, LayerNorm, Embedding,
batch_dot — python/mxnet/gluon/nn/basic_layers.py).

TPU-first design choices:
  * attention is ONE fused op (scaled-dot-product with stable softmax)
    lowered by XLA onto the MXU — not a chain of batch_dot/softmax eager
    ops; under hybridize()/SPMDTrainer the whole encoder is a single
    program;
  * bf16-friendly: all matmuls run in the param dtype; use net.cast
    ('bfloat16') + fp32 LayerNorm accumulations via XLA defaults;
  * sequence parallelism: pass ``seq_axis`` to route attention through
    parallel.ring_attention over a mesh 'seq' axis (capability beyond the
    reference, SURVEY §5.7);
  * tensor parallelism: FFN/attention projection weights match the
    classic Megatron sharding pattern (rules in ``tp_rules``).
"""
from __future__ import annotations

import contextlib as _contextlib
import math

import numpy as _np

from ..base import MXNetError
from ..gluon import nn
from ..gluon import loss as loss_mod
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import NDArray, _invoke

__all__ = ["MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderCell",
           "BERTEncoder", "BERTModel", "BERTForPretrain", "MLMPretrainLoss",
           "BERTMLMOnly", "bert_tiny", "bert_base", "bert_large",
           "tp_rules", "derive_tp_rules", "dense_attention",
           "cached_step_attn",
           "maybe_remat_cell"]


def _sdpa(q, k, v, num_heads, mask=None, seq_axis=None, mesh=None,
          causal=False, fuse_ok=True):
    """Fused scaled-dot-product attention op.

    q: (B, Tq, C), k/v: (B, Tk, C) NDArray (Tq == Tk for self-attention).
    Splits heads, runs stable softmax attention as one XLA program;
    ``mask`` is an optional (B, Tk) 0/1 key-validity mask; ``causal``
    adds the triangular decoder mask; with ``seq_axis`` uses ring
    attention over the mesh (sequence parallelism).  Shared by BERT and
    the NMT Transformer (models/transformer.py).
    """
    inputs = [q, k, v] + ([mask] if mask is not None else [])

    def fn(qv, kv, vv, *rest):
        import jax.numpy as jnp
        B, Tq, C = qv.shape
        Tk = kv.shape[1]
        hd = C // num_heads

        def split(x):
            return x.reshape(B, -1, num_heads, hd).transpose(0, 2, 1, 3)
        qh, kh, vh = split(qv), split(kv), split(vv)
        scale = 1.0 / math.sqrt(hd)
        if seq_axis is not None:
            from ..base import getenv
            sp_impl = (getenv("MXNET_SP_IMPL") or "ring").lower()
            if sp_impl == "ulysses":
                # all-to-all schedule (docs/parallelism.md: constant
                # collective count, needs heads % axis_size == 0)
                from ..parallel.ulysses import ulysses_attention
                from ..base import getenv_bool as _gb
                out = ulysses_attention(
                    qh, kh, vh, mesh=mesh, axis_name=seq_axis,
                    scale=scale, causal=causal,
                    mask=rest[0] if rest else None,
                    use_flash=fuse_ok and _gb("MXNET_USE_FUSION"))
            elif sp_impl == "ring":
                from ..parallel.ring import _ring_body
                from functools import partial
                from jax.sharding import PartitionSpec as P
                from jax import shard_map
                spec = P(None, None, seq_axis, None)
                from ..base import getenv_bool as _gb
                body = partial(_ring_body, axis_name=seq_axis,
                               scale=scale, causal=causal,
                               # blockwise (flash) local compute rides
                               # the same fusion gate as dense SDPA
                               use_flash=fuse_ok
                               and _gb("MXNET_USE_FUSION"))
                if rest:
                    # valid_length mask is sequence-sharded like K/V and
                    # rotates around the ring with them
                    out = shard_map(
                        body, mesh=mesh,
                        in_specs=(spec, spec, spec, P(None, seq_axis)),
                        out_specs=spec, check_vma=False)(qh, kh, vh,
                                                         rest[0])
                else:
                    out = shard_map(
                        body, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(qh, kh, vh)
            else:
                raise MXNetError(
                    f"MXNET_SP_IMPL={sp_impl!r} unknown; use 'ring' or "
                    "'ulysses'")
        else:
            from ..base import getenv_bool
            if (fuse_ok and qh.shape == kh.shape
                    and getenv_bool("MXNET_USE_FUSION")):
                # Pallas flash-attention kernel (reference env-var parity:
                # MXNET_USE_FUSION gates the fused-kernel tier,
                # src/operator/fusion/fused_op.cc); opt-in until the
                # kernel is profiled on the real chip.  The (B, Tk)
                # key-validity mask rides through the kernel as an
                # additive bias, so padded batches stay on the fused path.
                from ..kernels import flash_attention
                out = flash_attention(qh, kh, vh, scale=scale,
                                      causal=causal,
                                      mask=rest[0] if rest else None)
            else:
                s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
                if causal:
                    tri = jnp.tril(jnp.ones((Tq, Tk), jnp.bool_))
                    s = jnp.where(tri[None, None], s, -1e30)
                if rest:
                    s = jnp.where(rest[0][:, None, None, :] > 0, s, -1e30)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                out = jnp.einsum("bhqk,bhkd->bhqd",
                                 (p / l).astype(vh.dtype), vh)
        return out.transpose(0, 2, 1, 3).reshape(B, -1, C)
    return _invoke(fn, inputs, name="sdpa")


class MultiHeadAttention(HybridBlock):
    """Projected multi-head attention over _sdpa.  ``mem`` (optional third
    positional input) switches to cross-attention: keys/values project
    from ``mem`` while queries project from ``x``."""

    def __init__(self, units, num_heads, dropout=0.0, seq_axis=None,
                 mesh=None, causal=False, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError("num_heads must divide units")
        self._units = units
        self._num_heads = num_heads
        self._seq_axis = seq_axis
        self._mesh = mesh
        self._causal = causal
        with self.name_scope():
            self.query = nn.Dense(units, flatten=False, in_units=units)
            self.key = nn.Dense(units, flatten=False, in_units=units)
            self.value = nn.Dense(units, flatten=False, in_units=units)
            self.proj = nn.Dense(units, flatten=False, in_units=units)
            self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None, mem=None):
        kv_src = x if mem is None else mem
        q, k, v = self.query(x), self.key(kv_src), self.value(kv_src)
        out = _sdpa(q, k, v, self._num_heads, mask=mask,
                    seq_axis=self._seq_axis, mesh=self._mesh,
                    causal=self._causal)
        return self.dropout(self.proj(out))

    def project_kv(self, mem):
        """Precompute this head's K/V projections of an encoder memory —
        the cross-attention half of a KV cache (incremental decoding)."""
        return self.key(mem), self.value(mem)


def cached_step_attn(qv, kn, vn, ck, cv, tv, num_heads):
    """jax-level single-position attention over a KV cache, shared by the
    incremental decoders (transformer._DecoderCell.step, gpt.GPTCell.step):
    write this position's K/V at index ``tv``, attend causally over
    positions <= tv.  qv/kn/vn (B, 1, C); ck/cv (B, Tmax, C); returns
    (out (B, 1, C), ck', cv')."""
    import jax.numpy as jnp
    B, _, C = qv.shape
    hd = C // num_heads
    Tm = ck.shape[1]
    ck = ck.at[:, tv].set(kn[:, 0])
    cv = cv.at[:, tv].set(vn[:, 0])
    qh = qv.reshape(B, 1, num_heads, hd).transpose(0, 2, 1, 3)
    kh = ck.reshape(B, Tm, num_heads, hd).transpose(0, 2, 1, 3)
    vh = cv.reshape(B, Tm, num_heads, hd).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(hd)
    s = jnp.where(jnp.arange(Tm)[None, None, None, :] <= tv, s, -1e30)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = p / jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vh.dtype), vh)
    return out.transpose(0, 2, 1, 3).reshape(B, 1, C), ck, cv


@_contextlib.contextmanager
def dense_attention(net):
    """Temporarily run every attention cell of ``net`` on the dense
    (non-sequence-parallel) path.  Needed when a seq-parallel model must
    do a one-off eager forward on a single device — e.g. settling
    deferred parameter shapes before an SPMDTrainer builds — where the
    shard_map path cannot execute.  Shapes do not depend on the
    schedule, so the settled state is identical."""
    cells = []
    net.apply(lambda b: cells.append(b)
              if isinstance(b, MultiHeadAttention) else None)
    saved = [(c, c._seq_axis) for c in cells]
    try:
        for c in cells:
            c._seq_axis = None
        yield net
    finally:
        for c, s in saved:
            c._seq_axis = s


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn_1 = nn.Dense(hidden_size, flatten=False,
                                  in_units=units)
            self.ffn_2 = nn.Dense(units, flatten=False,
                                  in_units=hidden_size)
            self.dropout = nn.Dropout(dropout)
        self._activation = activation

    def hybrid_forward(self, F, x):
        h = self.ffn_1(x)
        h = F.gelu(h) if self._activation == "gelu" \
            else F.Activation(h, act_type=self._activation)
        return self.dropout(self.ffn_2(h))


class TransformerEncoderCell(HybridBlock):
    """Post-LN transformer layer (BERT style)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 seq_axis=None, mesh=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = MultiHeadAttention(units, num_heads, dropout,
                                                seq_axis, mesh)
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.ffn = PositionwiseFFN(units, hidden_size, dropout)
            self.ln2 = nn.LayerNorm(in_channels=units)

    def hybrid_forward(self, F, x, mask=None):
        x = self.ln1(x + self.attention(x, mask))
        x = self.ln2(x + self.ffn(x))
        return x


def maybe_remat_cell(cell, x, *rest):
    """Run one layer, optionally under ``jax.checkpoint``
    (``MXNET_BACKWARD_DO_MIRROR`` — the reference's mirror/memonger knob,
    docs/faq/env_var.md: trade recompute for activation memory).  Under
    the compiled paths (SPMDTrainer/hybridize via functional_call) the
    layer's internal activations are then rematerialized in the backward
    instead of saved — the standard seq-512/large-batch enabler on HBM.
    The eager-tape path records per-op, where a checkpoint boundary can't
    apply — plain call there."""
    from ..base import getenv_bool
    from .. import autograd as _ag
    if not getenv_bool("MXNET_BACKWARD_DO_MIRROR") or _ag.is_recording():
        return cell(x, *rest)
    import jax

    def f(xv):
        out = cell(NDArray(xv), *rest)
        if isinstance(out, tuple):      # e.g. MoE cells: (y, aux_loss)
            return tuple(o._data for o in out)
        return out._data
    out = jax.checkpoint(f)(x._data)
    if isinstance(out, tuple):
        return tuple(NDArray(o) for o in out)
    return NDArray(out)


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, seq_axis=None, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._cells = []
        with self.name_scope():
            for i in range(num_layers):
                cell = TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout, seq_axis, mesh)
                self.register_child(cell, f"layer{i}")

    def hybrid_forward(self, F, x, mask=None):
        for cell in self._children.values():
            x = maybe_remat_cell(cell, x, mask)
        return x


class BERTModel(HybridBlock):
    """Embeddings + encoder + pooler (reference workload: GluonNLP
    BERTModel).  forward(input_ids, token_types) -> (sequence_out,
    pooled_out)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 token_type_vocab=2, dropout=0.1, seq_axis=None, mesh=None,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units)
            self.token_type_embed = nn.Embedding(token_type_vocab, units)
            self.position_weight = self.params.get(
                "position_weight", shape=(max_length, units),
                init="normal")
            self.embed_ln = nn.LayerNorm(in_channels=units)
            self.embed_dropout = nn.Dropout(dropout)
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout, seq_axis, mesh)
            self.pooler = nn.Dense(units, activation="tanh",
                                   flatten=False, in_units=units)

    def hybrid_forward(self, F, input_ids, token_types, valid_length=None,
                       position_weight=None):
        T = input_ids.shape[1]
        emb = self.word_embed(input_ids) \
            + self.token_type_embed(token_types)
        pos = position_weight.slice_axis(0, 0, T).expand_dims(0)
        emb = self.embed_dropout(self.embed_ln(emb + pos))
        mask = None
        if valid_length is not None:
            ar = F.arange(0, T).reshape(1, -1)
            mask = (ar < valid_length.reshape(-1, 1)).astype("float32")
        seq = self.encoder(emb, mask)
        pooled = self.pooler(seq.slice_axis(1, 0, 1).squeeze(axis=1))
        return seq, pooled


class BERTForPretrain(HybridBlock):
    """MLM + NSP heads (reference workload: GluonNLP BERTForPretrain)."""

    def __init__(self, bert: BERTModel, vocab_size=30522, **kwargs):
        super().__init__(**kwargs)
        self._vocab_size = vocab_size
        with self.name_scope():
            self.bert = bert
            units = bert._units
            self.mlm_dense = nn.Dense(units, flatten=False,
                                      activation=None, in_units=units)
            self.mlm_ln = nn.LayerNorm(in_channels=units)
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False,
                                        in_units=units)
            self.nsp_classifier = nn.Dense(2, in_units=units)

    def hybrid_forward(self, F, input_ids, token_types, valid_length=None):
        seq, pooled = self.bert(input_ids, token_types, valid_length)
        h = F.gelu(self.mlm_dense(seq))
        mlm_scores = self.mlm_decoder(self.mlm_ln(h))
        nsp_scores = self.nsp_classifier(pooled)
        return mlm_scores, nsp_scores


class MLMPretrainLoss(HybridBlock):
    """Masked-LM cross-entropy over flattened (B*T, V) scores — the loss
    head the distributed examples and the multichip dryrun train with."""

    def __init__(self, vocab_size, **kwargs):
        super().__init__(**kwargs)
        self._vocab_size = vocab_size
        with self.name_scope():
            self.ce = loss_mod.SoftmaxCrossEntropyLoss()

    def hybrid_forward(self, F, mlm_scores, labels):
        return self.ce(mlm_scores.reshape(-1, self._vocab_size),
                       labels.reshape(-1))


class BERTPretrainLoss(HybridBlock):
    """Full pretraining loss: masked-LM CE + next-sentence CE (the anchor
    workload's objective — reference: GluonNLP scripts/bert pretraining
    loss = MLM + NSP).  Labels pack both targets in one (B, T+1) array:
    ``labels[:, :T]`` are per-token MLM targets, ``labels[:, T]`` the NSP
    class."""

    def __init__(self, vocab_size, **kwargs):
        super().__init__(**kwargs)
        self._vocab_size = vocab_size
        with self.name_scope():
            self.ce = loss_mod.SoftmaxCrossEntropyLoss()

    def hybrid_forward(self, F, mlm_scores, nsp_scores, labels):
        mlm_labels = labels[:, :-1]
        nsp_labels = labels[:, -1]
        mlm = self.ce(mlm_scores.reshape(-1, self._vocab_size),
                      mlm_labels.reshape(-1))
        nsp = self.ce(nsp_scores, nsp_labels)
        return mlm.mean() + nsp.mean()


class BERTMLMOnly(HybridBlock):
    """Wrap BERTForPretrain to expose only the MLM scores (single-output
    step function for SPMDTrainer)."""

    def __init__(self, inner, **kwargs):
        kwargs.setdefault("prefix", "")
        super().__init__(**kwargs)
        with self.name_scope():
            self.inner = inner

    def hybrid_forward(self, F, input_ids, token_types):
        mlm_scores, _nsp_scores = self.inner(input_ids, token_types)
        return mlm_scores


from ..parallel.spmd import exact_rule  # noqa: E402  (shared rule builder)


def derive_tp_rules(block, model_axis="model", extra=None):
    """Megatron TP rules derived from a BUILT model's ACTUAL parameter
    names: every MultiHeadAttention gets QKV column- / proj row-parallel,
    every PositionwiseFFN first-matmul column- / second-matmul
    row-parallel.  Name-exact, so custom ``prefix=`` models shard
    correctly (the regex fallbacks in each family's ``tp_rules`` key on
    the default auto-prefix names and would silently replicate a
    custom-prefixed model — SPMDTrainer warns when that happens).
    ``extra``: optional callable(block) -> list of rules appended per
    visited block (model-family hooks for embeddings/heads)."""
    from jax.sharding import PartitionSpec as P
    rules = []

    def visit(b):
        if isinstance(b, MultiHeadAttention):
            rules.extend(exact_rule(d.weight, P(model_axis, None))
                         for d in (b.query, b.key, b.value))
            rules.append(exact_rule(b.proj.weight, P(None, model_axis)))
        elif isinstance(b, PositionwiseFFN):
            rules.append(exact_rule(b.ffn_1.weight, P(model_axis, None)))
            rules.append(exact_rule(b.ffn_2.weight, P(None, model_axis)))
        elif isinstance(b, BERTForPretrain):
            rules.append(exact_rule(b.mlm_decoder.weight,
                                     P(model_axis, None)))
        elif isinstance(b, BERTModel):
            rules.append(exact_rule(b.word_embed.weight,
                                     P(None, model_axis)))
        if extra is not None:
            rules.extend(extra(b))

    block.apply(visit)
    if not rules:
        raise MXNetError("derive_tp_rules: no shardable layers under "
                         f"{type(block).__name__}")
    return rules


def core_tp_regex_rules(model_axis="model"):
    """The attention/FFN Megatron rules every transformer family shares
    (regexes over the DEFAULT auto-prefix names: dense0..2 =
    query/key/value, dense3 = proj — construction order; ffn dense0/1 =
    first/second matmul).  Each family's ``tp_rules`` appends its own
    embedding/head rules."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"multiheadattention\d+_dense[012]_weight", P(model_axis, None)),
        (r"multiheadattention\d+_dense3_weight", P(None, model_axis)),
        (r"positionwiseffn\d+_dense0_weight", P(model_axis, None)),
        (r"positionwiseffn\d+_dense1_weight", P(None, model_axis)),
    ]


def tp_rules(model_axis="model", block=None):
    """Megatron-style tensor-parallel sharding rules for SPMDTrainer:
    attention QKV + FFN first matmul column-parallel (axis 0 of the
    (out, in) Dense weight), attention proj + FFN second matmul
    row-parallel, MLM decoder column-parallel, word embedding sharded
    over the units axis.  The regexes target DEFAULT auto-prefix names;
    pass ``block=`` (the built net) to derive exact-name rules instead,
    required whenever any layer was built with a custom ``prefix=``
    (shard_params warns when a required rule goes dead)."""
    from jax.sharding import PartitionSpec as P
    if block is not None:
        return derive_tp_rules(block, model_axis)
    return core_tp_regex_rules(model_axis) + [
        # BERTForPretrain heads: dense0 = mlm_dense, dense1 = mlm_decoder
        # ((?#optional): a plain BERTModel has no pretrain head — exempt
        # from shard_params' dead-rule warning, invisible to re.search)
        (r"(?#optional)bertforpretrain\d+_dense1_weight",
         P(model_axis, None)),
        # BERTModel embeddings: embedding0 = word, embedding1 = token type
        (r"bertmodel\d+_embedding0_weight", P(None, model_axis)),
    ]


def bert_tiny(vocab_size=1024, max_length=128, **kw):
    return BERTModel(vocab_size=vocab_size, units=64, hidden_size=128,
                     num_layers=2, num_heads=2, max_length=max_length, **kw)


def bert_base(vocab_size=30522, **kw):
    return BERTModel(vocab_size=vocab_size, units=768, hidden_size=3072,
                     num_layers=12, num_heads=12, **kw)


def bert_large(vocab_size=30522, **kw):
    return BERTModel(vocab_size=vocab_size, units=1024, hidden_size=4096,
                     num_layers=24, num_heads=16, **kw)
