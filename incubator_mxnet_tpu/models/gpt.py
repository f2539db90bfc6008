"""Decoder-only causal language model (GPT-2 style; reference workload:
GluonNLP ``scripts/language_model`` + ``model.train.GPT2Model``, built —
like every model here — from this repo's op surface:
gluon.nn.Dense/LayerNorm/Embedding, python/mxnet/gluon/nn/basic_layers.py).

TPU-first design (mirrors models/bert.py and models/transformer.py):
  * pre-LN blocks; self-attention is the ONE fused SDPA op from bert.py,
    causal mask baked in statically — the whole stack is a single XLA
    program under hybridize/SPMDTrainer;
  * generation is a ``lax.scan`` over decode steps with per-layer KV
    caches in the carry (O(T) per step); ``use_cache=False`` re-runs the
    full prefix each step and is the tested oracle;
  * sampling (temperature / top-k) uses a threaded PRNG key in the scan
    carry — one compiled program, reproducible from mx.random.seed;
  * Megatron ``tp_rules`` + optional ``seq_axis`` ring/Ulysses attention
    make the same model the long-context/multichip workload.
"""
from __future__ import annotations

import math

import numpy as _np

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import NDArray, _invoke
from .bert import MultiHeadAttention, PositionwiseFFN, maybe_remat_cell

__all__ = ["GPTCell", "GPTModel", "gpt_tiny", "gpt2_124m", "tp_rules"]


class GPTCell(HybridBlock):
    """Pre-LN decoder block: x + attn(ln1(x)), then x + ffn(ln2(x))."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 seq_axis=None, mesh=None, moe_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25, **kwargs):
        super().__init__(**kwargs)
        self._moe = int(moe_experts) > 0
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.attention = MultiHeadAttention(
                units, num_heads, dropout, causal=True,
                seq_axis=seq_axis, mesh=mesh)
            self.ln2 = nn.LayerNorm(in_channels=units)
            if self._moe:
                from .moe import MoEFFN
                self.ffn = MoEFFN(units, hidden_size, moe_experts,
                                  top_k=moe_top_k,
                                  capacity_factor=moe_capacity_factor)
                # MoEFFN is dropout-free inside (the routed einsums are
                # pure); regularize the combined output instead — the
                # Megatron-MoE placement
                self.moe_drop = nn.Dropout(dropout)
            else:
                self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                           activation="gelu")

    def hybrid_forward(self, F, x, valid=None):
        x = x + self.attention(self.ln1(x))
        if self._moe:
            if valid is None:
                y, aux = self.ffn(self.ln2(x))
            else:
                y, aux = self.ffn(self.ln2(x), valid)
            return x + self.moe_drop(y), aux
        return x + self.ffn(self.ln2(x))

    def prime(self, x):
        """Full-prefix forward that ALSO returns this layer's K/V
        projections — fills the generation cache in one pass, projecting
        each of Q/K/V exactly once (the plain forward would recompute
        K/V inside the attention block)."""
        from .bert import _sdpa
        at = self.attention
        h = self.ln1(x)
        q, k, v = at.query(h), at.key(h), at.value(h)
        out = _sdpa(q, k, v, at._num_heads, causal=True)
        x = x + at.dropout(at.proj(out))
        return x + self._ffn_out(self.ln2(x)), k, v

    def _ffn_out(self, h):
        """FFN output with the MoE aux loss discarded — the generation
        paths are inference-only, where only the activations matter."""
        if self._moe:
            return self.ffn(h)[0]
        return self.ffn(h)

    # -- the serving seam (docs/serving.md "The layer interface") -------
    def serve_prefill(self, h, positions, live=None):
        """A whole prompt with nothing cached, over jax arrays: h
        (B, T, C) -> ``(h', k, v)``, k and v (B, T, heads, D).  The body
        is :meth:`prime`; learned positions were added at the embedding,
        and padding costs nothing to mask here."""
        del positions, live
        out, k, v = self.prime(NDArray(h))
        B, T, _ = h.shape
        H = self.attention._num_heads
        return (out._data, k._data.reshape(B, T, H, -1),
                v._data.reshape(B, T, H, -1))

    def serve_cached(self, h, positions, attend, live=None):
        """Positions that attend through the engine's cache:
        ``attend(q, k, v)`` (each (B, T, heads, D)) writes k and v where
        the engine's program says and returns q's attention over what
        the cache then holds.  Returns ``(h', counts)``; this layer
        counts nothing."""
        del positions, live
        at = self.attention
        x = NDArray(h)
        hn = self.ln1(x)
        q, kn, vn = at.query(hn), at.key(hn), at.value(hn)
        B, T, C = h.shape
        H = at._num_heads
        attn = attend(q._data.reshape(B, T, H, -1),
                      kn._data.reshape(B, T, H, -1),
                      vn._data.reshape(B, T, H, -1))
        out_nd = NDArray(attn.reshape(B, T, C).astype(h.dtype))
        x = x + at.dropout(at.proj(out_nd))
        x = x + self._ffn_out(self.ln2(x))
        return x._data, {}

    def step(self, x, cache_k, cache_v, t):
        """One-position incremental step: x (B, 1, C) at position ``t``,
        cache_k/v (B, Tmax, C) holding positions < t.  Returns
        (y (B, 1, C), cache_k', cache_v')."""
        import functools
        from .bert import cached_step_attn
        at = self.attention
        h = self.ln1(x)
        q, k_new, v_new = at.query(h), at.key(h), at.value(h)
        out, ck, cv = _invoke(
            functools.partial(cached_step_attn, num_heads=at._num_heads),
            [q, k_new, v_new, cache_k, cache_v, t], name="gpt_step_attn")
        out = x + at.dropout(at.proj(out))
        return out + self._ffn_out(self.ln2(out)), ck, cv


class GPTModel(HybridBlock):
    """Token + LEARNED position embeddings -> N GPTCells -> final LN ->
    tied LM head (logits through the embedding matrix, GPT-2's tying)."""

    def __init__(self, vocab_size, units=128, hidden_size=512,
                 num_layers=2, num_heads=2, max_length=256, dropout=0.1,
                 seq_axis=None, mesh=None, moe_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25, **kwargs):
        super().__init__(**kwargs)
        self._vocab_size = vocab_size
        self._units = units
        self._max_length = max_length
        self._moe = int(moe_experts) > 0
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.pos_embed = nn.Embedding(max_length, units)
            self.drop = nn.Dropout(dropout)
            self.cells = nn.HybridSequential()
            for _ in range(num_layers):
                self.cells.add(GPTCell(
                    units, hidden_size, num_heads, dropout,
                    seq_axis=seq_axis, mesh=mesh, moe_experts=moe_experts,
                    moe_top_k=moe_top_k,
                    moe_capacity_factor=moe_capacity_factor))
            self.ln_f = nn.LayerNorm(in_channels=units)

    # -- helpers -------------------------------------------------------
    def _positions(self, ids, offset=0):
        def fn(iv):
            import jax.numpy as jnp
            T = iv.shape[1]
            return jnp.broadcast_to(
                jnp.arange(offset, offset + T, dtype=jnp.int32)[None],
                iv.shape)
        return _invoke(fn, [ids], name="gpt_positions")

    def _embed_at(self, ids, offset=0):
        x = self.embed(ids) + self.pos_embed(self._positions(ids, offset))
        return self.drop(x)

    def _project(self, x):
        """Tied LM head: logits = x @ E^T.  The embedding Parameter's own
        NDArray goes into the op, so the eager autograd tape reaches it —
        a fresh wrapper would silently drop the head's gradient."""
        w = self.embed.weight.data()
        return _invoke(_lm_logits, [x, w], name="gpt_lm_head")

    def hybrid_forward(self, F, ids):
        if ids.shape[1] > self._max_length:
            raise MXNetError(
                f"sequence length {ids.shape[1]} exceeds max_length "
                f"{self._max_length}")
        x = self._embed_at(ids)
        aux_total = None
        for cell in self.cells._children.values():
            out = maybe_remat_cell(cell, x)
            if cell._moe:
                x, aux = out
                aux_total = aux if aux_total is None else aux_total + aux
            else:
                x = out
        logits = self._project(self.ln_f(x))
        if self._moe:
            # SUM over MoE layers (the Switch recipe): loss adds
            # aux_weight * aux once, regardless of depth
            return logits, aux_total
        return logits

    # -- the serving seam (docs/serving.md "The layer interface") -------
    #: integer counters the layers return from ``serve_cached``: none
    serve_counters = ()

    def kv_layout(self):
        """What the layers cache per position (``serving.kvcache.KVLayout``):
        every head its own K/V, float32 pools, no window."""
        cells = list(self.cells._children.values())
        heads = cells[0].attention._num_heads
        return {"num_layers": len(cells), "kv_heads": heads,
                "head_dim": self._units // heads, "dtype": "float32",
                "windows": (None,) * len(cells),
                "max_length": self._max_length}

    def serve_layers(self):
        return list(self.cells._children.values())

    def serve_embed(self, tokens, positions):
        """tokens, positions (B, T) int32 jax arrays -> h (B, T, C): the
        token embedding plus the LEARNED position's."""
        x = self.embed(NDArray(tokens)) + self.pos_embed(NDArray(positions))
        return self.drop(x)._data

    def serve_head(self, h):
        """h (B, T, C) -> logits (B, T, vocab): final LayerNorm, tied
        head."""
        return self._project(self.ln_f(NDArray(h)))._data

    # -- pipeline parallelism ------------------------------------------
    def pipeline_split(self):
        """Stage protocol for ``parallel.PipelineTrainer`` (reached via
        ``SPMDTrainer(..., pipeline_axis=...)``): returns
        ``(first_params, first_fn, cells, last_params, last_fn)``.
        Stage 0 owns the embeddings (``first_fn`` embeds a microbatch of
        ids into (b, T, C)); every stage runs its contiguous slice of
        ``cells``; the last stage applies the final LayerNorm and the
        TIED LM head — the embedding matrix arrives back via
        ``first_vals`` so the tying (and both gradient contributions,
        summed by the pipe-axis psum) is preserved.  Requires
        dropout=0 (the trainer enforces the pure-stage contract)."""
        import jax

        if self._moe:
            raise MXNetError(
                "pipeline_split does not yet support MoE cells (the "
                "stage protocol carries one activation tensor, not the "
                "aux loss); use expert parallelism (ep_rules) instead")

        first_params = [self.embed.weight, self.pos_embed.weight]
        max_length = self._max_length

        def first_fn(vals, ids):
            import jax.numpy as jnp
            E, Ppos = vals
            T = ids.shape[-1]
            if T > max_length:       # static shape — trace-time guard,
                raise MXNetError(    # same contract as hybrid_forward
                    f"sequence length {T} exceeds max_length "
                    f"{max_length}")
            pos = Ppos[jnp.arange(T)][None]
            return E[ids] + pos.astype(E.dtype)

        cells = list(self.cells._children.values())
        ln = self.ln_f
        last_params = [ln.gamma, ln.beta]
        key = jax.random.PRNGKey(0)     # LN consumes no randomness

        def last_fn(vals, first_vals, xv):
            from ..gluon.block import functional_call
            outs, _ = functional_call(ln, last_params, list(vals),
                                      [], [], [NDArray(xv)], False, key)
            return _lm_logits(outs[0], first_vals[0])

        return first_params, first_fn, cells, last_params, last_fn

    # -- generation ----------------------------------------------------
    def generate(self, ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, top_p=0.0, use_cache=True, seed=None):
        """Autoregressive continuation of prompt ``ids`` (B, Tp) int32.

        temperature == 0 -> greedy; otherwise softmax sampling at that
        temperature, restricted to the ``top_k`` highest logits when
        top_k > 0 and/or to the nucleus of smallest cumulative
        probability mass >= ``top_p`` when 0 < top_p < 1 (the top-1
        token always survives; both filters compose, top-k first).  One ``lax.scan`` program either way; ``use_cache``
        False re-runs the full prefix per step (the oracle).  Returns
        (B, Tp + max_new_tokens) int32 tokens.

        MoE models: padding positions are masked out of the router (they
        claim no expert capacity), so cached == full-prefix holds in the
        no-drop regime (ample ``moe_capacity_factor``).  Under capacity
        pressure the two paths form different routing groups (prefill
        routes B*Tp tokens at once, a decode step routes B) and may drop
        different tokens — inherent to capacity-based GShard routing,
        exactly as train-time vs incremental-serve routing differs in
        the public Switch/GShard implementations."""
        B, Tp = ids.shape
        total = Tp + max_new_tokens
        if total > self._max_length:
            raise MXNetError(
                f"prompt {Tp} + {max_new_tokens} new tokens exceeds "
                f"max_length {self._max_length}")
        if max_new_tokens < 0:
            raise MXNetError(
                f"max_new_tokens={max_new_tokens} is negative (a "
                "miscomputed budget?); use 0 for no-op generation")
        if max_new_tokens == 0:
            return ids
        from .. import random as _random
        key = _random.new_key() if seed is None else None
        if seed is not None:
            import jax
            key = jax.random.PRNGKey(seed)
        if use_cache:
            return self._generate_cached(ids, max_new_tokens, temperature,
                                         top_k, top_p, key)
        return self._generate_full(ids, max_new_tokens, temperature,
                                   top_k, top_p, key)

    def _sample_fn(self, temperature, top_k, top_p=0.0):
        if not 0.0 <= float(top_p) <= 1.0:
            raise MXNetError(f"top_p={top_p} outside [0, 1]")

        def pick(logits, key):
            import jax
            import jax.numpy as jnp
            lf = logits.astype(jnp.float32)
            if temperature <= 0.0:
                return jnp.argmax(lf, axis=-1).astype(jnp.int32)
            lf = lf / temperature
            k = min(int(top_k), lf.shape[-1]) if top_k else 0
            need_sort = (k > 0 and k < lf.shape[-1]) or 0.0 < top_p < 1.0
            if need_sort:
                # ONE descending sort feeds both filters (the nucleus
                # runs on the already-top-k-masked order: -inf entries
                # carry zero probability mass, so they can never be
                # kept or become the cutoff)
                srt = -jnp.sort(-lf, axis=-1)
            if k > 0 and k < lf.shape[-1]:
                # top_k >= vocab degenerates to plain sampling (GPT-2
                # convention) rather than an out-of-bounds sort index
                kth = srt[..., k - 1][..., None]
                lf = jnp.where(lf >= kth, lf, -jnp.inf)
                srt = jnp.where(jnp.arange(srt.shape[-1]) < k, srt,
                                -jnp.inf)
            if 0.0 < top_p < 1.0:
                # nucleus filter: keep the smallest prefix of the
                # descending-prob sort whose mass reaches top_p; the
                # exclusive cumsum keeps the top-1 token unconditionally
                probs = jax.nn.softmax(srt, axis=-1)
                before = jnp.cumsum(probs, axis=-1) - probs
                keep = before < top_p
                cutoff = jnp.min(jnp.where(keep, srt, jnp.inf),
                                 axis=-1, keepdims=True)
                lf = jnp.where(lf >= cutoff, lf, -jnp.inf)
            return jax.random.categorical(key, lf, axis=-1).astype(
                jnp.int32)
        return pick

    def _generate_full(self, ids, n_new, temperature, top_k, top_p,
                       key):
        """Oracle: whole prefix re-run per step, lax.scan outside."""
        pick = self._sample_fn(temperature, top_k, top_p)
        B, Tp = ids.shape
        total = Tp + n_new

        # pad to the full length once; scan carries (tokens, t, key)
        def fn(iv):
            import jax
            import jax.numpy as jnp

            toks0 = jnp.zeros((B, total), jnp.int32)
            toks0 = jax.lax.dynamic_update_slice(toks0, iv, (0, 0))

            def body(carry, _):
                toks, t, k = carry
                logits = self._fwd_tokens(toks, n_valid=t)  # (B, total, V)
                last = jnp.take_along_axis(
                    logits, (t - 1)[None, None, None].astype(jnp.int32)
                    .repeat(B, 0), axis=1)[:, 0]
                k, sub = jax.random.split(k)
                nxt = pick(last, sub)
                toks = toks.at[:, t].set(nxt)
                return (toks, t + 1, k), None

            (toks, _, _), _ = jax.lax.scan(
                body, (toks0, jnp.int32(Tp), key), None, length=n_new)
            return toks
        return _invoke(fn, [ids], name="gpt_generate_full")

    def _fwd_tokens(self, toks, n_valid=None):
        """jax-level forward over already-jax tokens (inside scan).
        ``n_valid`` (scalar, may be traced) marks how many leading
        positions hold real tokens: causal attention already ignores
        the zero-padded tail, but MoE routing would otherwise let
        padding claim expert capacity away from real tokens."""
        import jax.numpy as jnp
        x = self.embed.weight.data()._data[toks]
        pos = self.pos_embed.weight.data()._data[
            jnp.arange(toks.shape[1])]
        x = (x + pos[None].astype(x.dtype))
        xn = NDArray(x)
        valid = None
        if n_valid is not None and self._moe:
            valid = NDArray(jnp.broadcast_to(
                (jnp.arange(toks.shape[1]) < n_valid)[None], toks.shape)
                .astype(jnp.float32))
        for cell in self.cells._children.values():
            if cell._moe:
                xn = (cell(xn) if valid is None else cell(xn, valid))[0]
            else:
                xn = cell(xn)
        out = self.ln_f(xn)
        return _lm_logits(out._data, self.embed.weight.data()._data)

    def _generate_cached(self, ids, n_new, temperature, top_k, top_p,
                         key):
        pick = self._sample_fn(temperature, top_k, top_p)
        B, Tp = ids.shape
        total = Tp + n_new
        C = self._units
        cells = list(self.cells._children.values())

        # prime: one full-prefix pass filling each layer's cache
        x = self._embed_at(ids)
        caches = []
        for cell in cells:
            x, k_proj, v_proj = cell.prime(x)
            ck = _invoke(
                lambda kv: _pad_cache(kv, total), [k_proj],
                name="gpt_cache_pad")
            cv = _invoke(
                lambda kv: _pad_cache(kv, total), [v_proj],
                name="gpt_cache_pad")
            caches.append((ck, cv))
        logits_p = self._project(self.ln_f(x))

        def fn(iv, lp, *flat):
            import jax
            import jax.numpy as jnp
            cks = flat[0::2]
            cvs = flat[1::2]

            toks0 = jnp.zeros((B, total), jnp.int32)
            toks0 = jax.lax.dynamic_update_slice(toks0, iv, (0, 0))
            k0, sub0 = jax.random.split(key)
            first = pick(lp[:, -1], sub0)
            toks0 = toks0.at[:, Tp].set(first)

            def body(carry, _):
                toks, t, k, caches_c = carry
                # the token at position t is the newest one; its logits
                # produce position t+1
                cur = jnp.take_along_axis(
                    toks, jnp.broadcast_to(
                        t.reshape(1, 1), (B, 1)).astype(jnp.int32),
                    axis=1)
                xn = NDArray(
                    self.embed.weight.data()._data[cur]
                    + self.pos_embed.weight.data()._data[t][None, None])
                new_caches = []
                for cell, (ck, cv) in zip(cells, caches_c):
                    xn, ck2, cv2 = cell.step(
                        xn, NDArray(ck), NDArray(cv), NDArray(t))
                    new_caches.append((ck2._data, cv2._data))
                out = self.ln_f(xn)
                logits = _lm_logits(
                    out._data, self.embed.weight.data()._data)[:, 0]
                k, sub = jax.random.split(k)
                nxt = pick(logits, sub)
                toks = toks.at[:, t + 1].set(nxt)
                return (toks, t + 1, k, tuple(new_caches)), None

            caches_c = tuple((ck, cv) for ck, cv in zip(cks, cvs))
            (toks, _, _, _), _ = jax.lax.scan(
                body, (toks0, jnp.int32(Tp), k0, caches_c), None,
                length=max(n_new - 1, 0))
            return toks

        flat = []
        for ck, cv in caches:
            flat += [ck, cv]
        return _invoke(fn, [ids, logits_p] + flat, name="gpt_generate")


def _lm_logits(xv, wv):
    """The tied-head einsum, jax-level — the ONE definition every logits
    site (training forward, full-prefix oracle, cached scan body) uses."""
    import jax.numpy as jnp
    return jnp.einsum("btc,vc->btv", xv, wv.astype(xv.dtype))


def _pad_cache(kv, total):
    import jax.numpy as jnp
    B, Tp, C = kv.shape
    pad = jnp.zeros((B, total - Tp, C), kv.dtype)
    return jnp.concatenate([kv, pad], axis=1)


def tp_rules(model_axis="model", block=None):
    """Megatron sharding for SPMDTrainer (same spirit as bert.tp_rules):
    attention QKV + first FFN matmul column-parallel, attention proj +
    second FFN matmul row-parallel, embeddings row-sharded over vocab.
    Pass ``block=`` (the built net) for exact-name rules — required with
    custom ``prefix=`` models, where the auto-prefix regexes below would
    silently replicate the weights (SPMDTrainer warns on dead rules)."""
    from jax.sharding import PartitionSpec as P
    if block is not None:
        from .bert import derive_tp_rules, exact_rule

        def gpt_extra(b):
            if isinstance(b, GPTModel):
                return [exact_rule(b.embed.weight, P(model_axis, None))]
            return []
        return derive_tp_rules(block, model_axis, extra=gpt_extra)
    from .bert import core_tp_regex_rules
    return core_tp_regex_rules(model_axis) + [
        (r"gptmodel\d+_embedding0_weight", P(model_axis, None)),
    ]


def gpt_tiny(vocab_size=512, **kwargs):
    kwargs.setdefault("units", 64)
    kwargs.setdefault("hidden_size", 128)
    kwargs.setdefault("num_layers", 2)
    kwargs.setdefault("num_heads", 2)
    kwargs.setdefault("max_length", 128)
    return GPTModel(vocab_size, **kwargs)


def gpt2_124m(vocab_size=50257, **kwargs):
    """GPT-2 small (124M): 12 layers, 768 units, 12 heads, ctx 1024."""
    kwargs.setdefault("units", 768)
    kwargs.setdefault("hidden_size", 3072)
    kwargs.setdefault("num_layers", 12)
    kwargs.setdefault("num_heads", 12)
    kwargs.setdefault("max_length", 1024)
    return GPTModel(vocab_size, **kwargs)
