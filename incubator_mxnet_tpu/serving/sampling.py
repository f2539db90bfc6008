"""Sampling plane — in-program stochastic decoding for the serving
stack (docs/serving.md "Sampling").

The generation programs in :mod:`serving.engine` are a CLOSED compiled
set; sampling must not reopen it.  Everything here is therefore either
a **traced operand** of the existing programs (per-slot temperature /
top-k / top-p / logit-bias row / RNG root key — data, never shape) or
pure host-side bookkeeping (stop sequences, constrained-output masks).

Determinism is the whole design.  Each slot carries a *root* RNG key
derived from the request seed; the key that samples the token at
sequence position ``t`` is ``step_keys(root, t)`` — the position XORed
into the root's low word — computed in-program from the position
operand (the position IS the per-step key stream: the burst scan's
position carry advances it step by step).
Because the key depends only on ``(root, position)`` — never on which
program produced the logits — the per-step decode, the scanned burst,
and the speculative verify all draw the SAME gumbel noise for the same
position, which is what makes seeded runs bit-identical across every
dispatch path and at any speculative accept rate (the Gumbel-coupled
acceptance argument in ``GenerationEngine.spec_step``).

Sampling itself is keyed Gumbel-max, branchless PER SLOT: filter the
biased logits to the top-k/top-p support, add gumbel noise from the
position key, argmax; ``temperature == 0`` selects the plain biased
argmax via ``jnp.where``, so a greedy slot emits bit-identical tokens
to the pre-sampling programs whatever its neighbours do.  Around the
slots there is ONE branch a step, of the whole batch
(:func:`sample_tokens`): ``lax.cond(any(temperature > 0 & live), full,
greedy)``, read off the program's own operands.  A step in which no
live slot samples takes the argmax alone — no stable sort, softmax,
cumulative sum or Gumbel noise over ``[slots, vocabulary]``, which
``where`` would compute and throw away (a fifth to a third of a greedy
decode step on the chip, PERF.md PR 32).  The predicate is a scalar
OUTSIDE the ``vmap``: a ``cond`` under ``vmap`` with a per-slot
predicate lowers to a ``select`` that runs both sides.  It is masked by
``live`` because a released slot keeps the temperature of the request
that last held it (``GenerationEngine.set_slot_sampling``): unmasked,
one sampled request would hold every later greedy batch on the full
branch until its slot is joined again.  Both branches live in the same
program: the compiled set stays closed.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

import numpy as _np

from ..base import MXNetError

__all__ = ["SamplingParams", "root_key", "derive_candidate_seed",
           "step_keys", "sample_tokens", "topn_logprobs", "stop_trim",
           "JsonMaskMachine", "MASK_OFF"]

# Disallowed tokens get this logit bias: decisively below any real
# logit, but finite — a fully-masked row must degrade to a defined
# argmax, never a NaN softmax (-inf - -inf) inside a compiled program.
MASK_OFF = -1e9


# ---------------------------------------------------------------------------
# request-level parameters
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.  The default instance is
    exactly the pre-sampling greedy contract: ``temperature == 0``
    decodes argmax, every other field inert.

    ``stop`` is a tuple of token-id sequences (the serving API speaks
    token ids); detection happens host-side at the emit boundary, and
    the matched stop sequence itself stays in the output.  ``seed``
    None + ``temperature > 0`` means the server picks (and echoes) one
    — a sampled response is always replayable."""

    temperature: float = 0.0
    top_k: int = 0                  # 0: no top-k filter
    top_p: float = 1.0              # 1.0: no nucleus filter
    seed: Optional[int] = None
    logprobs: int = 0               # top-N per-token logprobs (0: off)
    stop: Tuple[Tuple[int, ...], ...] = ()
    n: int = 1                      # candidate fan-out over slots
    logit_bias: Optional[Dict[int, float]] = None
    json_mode: bool = False

    @property
    def sampled(self) -> bool:
        return float(self.temperature) > 0.0

    def validate(self, *, max_stops: int = 4, max_stop_len: int = 16,
                 max_n: int = 8) -> "SamplingParams":
        """Range-check every field (ValueError → HTTP 400) and return
        a canonicalized copy (stop sequences as int tuples)."""
        if not float(self.temperature) >= 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if int(self.top_k) < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < float(self.top_p) <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}")
        if self.seed is not None and not 0 <= int(self.seed) < 2 ** 63:
            raise ValueError(f"seed must be in [0, 2**63), got "
                             f"{self.seed}")
        if int(self.logprobs) < 0:
            raise ValueError(
                f"logprobs must be >= 0, got {self.logprobs}")
        stops = []
        for s in self.stop or ():
            seq = tuple(int(t) for t in
                        (s if isinstance(s, (list, tuple)) else (s,)))
            if not seq:
                raise ValueError("stop sequences must be non-empty")
            if len(seq) > int(max_stop_len):
                raise ValueError(
                    f"stop sequence length {len(seq)} exceeds "
                    f"{max_stop_len}")
            stops.append(seq)
        if len(stops) > int(max_stops):
            raise ValueError(
                f"{len(stops)} stop sequences exceed the limit of "
                f"{max_stops} (MXNET_SAMPLING_MAX_STOPS)")
        if not 1 <= int(self.n) <= int(max_n):
            raise ValueError(f"n must be in [1, {max_n}], got {self.n}")
        if self.logit_bias:
            for t, b in self.logit_bias.items():
                int(t), float(b)    # TypeError/ValueError → HTTP 400
        return replace(self, temperature=float(self.temperature),
                       top_k=int(self.top_k), top_p=float(self.top_p),
                       logprobs=int(self.logprobs), n=int(self.n),
                       stop=tuple(stops))


def root_key(seed: int) -> _np.ndarray:
    """The slot's RNG root as a host uint32 pair — bit-identical to
    ``jax.random.PRNGKey(seed)`` (legacy threefry seeding) without a
    device dispatch.  PRNGKey derives the high word from the seed's
    upper 32 bits only under ``jax_enable_x64``; replicating that keeps
    the replay contract exact either way."""
    import jax
    s = int(seed) & ((1 << 64) - 1)
    high = (s >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return _np.array([high, s & 0xFFFFFFFF], _np.uint32)


def derive_candidate_seed(seed: int, candidate: int) -> int:
    """Seed for candidate ``i`` of an n>1 fan-out.  Candidate 0 keeps
    the request seed unchanged, so an ``n=1`` rerun of the echoed seed
    replays candidate 0 byte-for-byte."""
    if candidate == 0:
        return int(seed)
    return (int(seed) + 0x9E3779B97F4A7C15 * int(candidate)) % (2 ** 63)


# ---------------------------------------------------------------------------
# traced sampling (called from inside the engine's compiled programs)
# ---------------------------------------------------------------------------

def step_keys(root_keys, indices):
    """Per-slot sampling keys for the tokens at sequence positions
    ``indices``: ``(hi, lo XOR index)``.  The per-draw threefry hash in
    :func:`_sample_row` mixes the key words with the counter, so
    XOR-ing the position into the low word is a full stream split —
    a second ``fold_in`` hash here would buy nothing but an extra
    threefry round compiled into EVERY decode/prefill/verify program
    (measured ~15% of engine warmup).  Broadcasting: ``root_keys``
    (..., 2) uint32 against ``indices`` (...,) int, so the decode step
    (S,), the prefill scalar, and the verify grid (S, Q) all share this
    ONE derivation — bit-identity across paths by construction."""
    import jax.numpy as jnp
    idx = jnp.asarray(indices).astype(jnp.uint32)
    return jnp.stack([jnp.broadcast_to(root_keys[..., 0], idx.shape),
                      root_keys[..., 1] ^ idx], axis=-1)


def _gumbel_row(key, V):
    """Keyed Gumbel noise (V,) from a counter-based integer hash: two
    murmur3 finalizer rounds over (lane, key) — full 32-bit avalanche
    per round, and a pure function of ``(key, lane)`` so every dispatch
    path that derives the same :func:`step_keys` key draws the SAME
    noise.  ``jax.random.uniform`` here would be distributionally
    nicer-pedigreed but compiles a threefry tower into EVERY serving
    program (~1s of engine warmup each, measured); sampling needs an
    unpredictable tie-break, not a cryptographic stream."""
    import jax.numpy as jnp
    x = jnp.arange(V, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
    x = x ^ key[1]
    for salt in (key[0], key[1]):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16) ^ salt
    # top 24 bits → uniform in [2^-24, 1]; the floor keeps log finite
    u = jnp.maximum(x >> 8, 1).astype(jnp.float32) * (2.0 ** -24)
    return -jnp.log(-jnp.log(u))


def _sample_row(lg, temperature, top_k, top_p, bias, key):
    """One slot: biased logits (V,) → sampled token id (scalar int32).
    Branchless — ``temperature == 0`` selects the biased argmax via
    ``where``, so a greedy slot of a batch that samples is bit-identical
    to the pre-sampling ``jnp.argmax`` (the batch's one branch, which
    skips all of this when no live slot samples, is
    :func:`sample_tokens`).  Filter conventions follow
    ``models/gpt.py:_sample_fn``: temperature scales before the
    filters, ``top_k <= 0`` (or >= vocab) disables top-k, and the
    nucleus filter's exclusive cumsum keeps the top-1 token
    unconditionally, so the masked support is never empty."""
    import jax
    import jax.numpy as jnp
    V = lg.shape[-1]
    lgb = (lg + bias).astype(jnp.float32)
    greedy = jnp.argmax(lgb, axis=-1).astype(jnp.int32)
    z = lgb / jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    srt = jnp.sort(z)[::-1]
    kk = jnp.where(top_k <= 0, V, jnp.minimum(top_k, V))
    keep = z >= srt[kk - 1]
    probs = jax.nn.softmax(srt)
    before = jnp.cumsum(probs) - probs        # exclusive: before[0]==0
    cutoff = jnp.min(jnp.where(before < top_p, srt, jnp.inf))
    keep &= z >= cutoff
    sampled = jnp.argmax(jnp.where(keep, z, MASK_OFF)
                         + _gumbel_row(key, V),
                         axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _greedy(logits, biases):
    """The greedy branch's whole batch: the biased argmax over the last
    axis, int32 — the expression :func:`_sample_row` selects for a
    ``temperature == 0`` slot, so the branch taken never shows in a
    greedy token."""
    import jax.numpy as jnp
    return jnp.argmax((logits + biases).astype(jnp.float32),
                      axis=-1).astype(jnp.int32)


def sample_tokens(logits, temperatures, top_ks, top_ps, biases, keys,
                  live=None):
    """Per-slot keyed Gumbel-max sampling behind the batch's one branch:
    ``logits`` (S, V) → token ids (S,) int32.  All parameters are traced
    operands — ``temperatures``/``top_ks``/``top_ps`` (S,), ``biases``
    (S, V), ``keys`` (S, 2) uint32 from :func:`step_keys`, ``live`` (S,)
    bool the slots whose token anyone reads (None: all).  Also the
    verify grid — ``logits`` (S, Q, V), ``keys`` (S, Q, 2), a slot's Q
    positions sharing its parameters → (S, Q) — and a prefill's one row
    — ``logits`` (V,), scalar parameters → ().

    ``full`` is :func:`_sample_row` over every slot, greedy ones
    included; ``greedy`` the argmax it would have selected for them.
    The scalar predicate keeps ``lax.cond`` a real conditional (module
    docstring): where no live slot samples, the sort never runs."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    row, greedy_biases = _sample_row, biases
    if logits.ndim == 3:
        row = jax.vmap(row, in_axes=(0, None, None, None, None, 0))
        greedy_biases = biases[:, None, :]
    if logits.ndim > 1:
        row = jax.vmap(row)
    sampling = temperatures > 0.0
    if live is not None:
        sampling &= live
    return lax.cond(
        jnp.any(sampling),
        lambda: row(logits, temperatures, top_ks, top_ps, biases, keys),
        lambda: _greedy(logits, greedy_biases))


def topn_logprobs(logits, biases, n: int):
    """Top-``n`` per-token logprobs of the biased distribution:
    ``(values (..., n) f32, token ids (..., n) int32)``.  ``n`` is
    baked at engine construction (``MXNET_SAMPLING_LOGPROBS_TOPN``) so
    the output arity — and with it the compiled program set — never
    varies per request; per-request N is a host-side slice."""
    import jax
    import jax.numpy as jnp
    lp = jax.nn.log_softmax((logits + biases).astype(jnp.float32),
                            axis=-1)
    vals, ids = jax.lax.top_k(lp, int(n))
    return vals, ids.astype(jnp.int32)


# ---------------------------------------------------------------------------
# host-side planes: stop sequences and constrained output
# ---------------------------------------------------------------------------

def stop_trim(prev_tail, new_tokens, stops):
    """Scan ``new_tokens`` (appended after ``prev_tail``) for the
    first completion of any stop sequence.  Returns ``(kept,
    stopped)``: keep the first ``kept`` new tokens (the stop sequence
    itself stays in the output) and discard the rest — the burst
    over-generation path (``docs/serving.md``; the discarded tail's
    K/V writes were already null-block-redirected in-program)."""
    if not stops:
        return len(new_tokens), False
    window = max(len(s) for s in stops)
    tail = list(prev_tail)[-(window - 1):] if window > 1 else []
    for i, t in enumerate(new_tokens):
        tail.append(int(t))
        for s in stops:
            if len(tail) >= len(s) and tuple(tail[-len(s):]) == tuple(s):
                return i + 1, True
        if len(tail) > window:
            del tail[0]
    return len(new_tokens), False


class JsonMaskMachine:
    """Constrained-output state machine: a character-level pushdown
    automaton over (a useful subset of) the JSON grammar, driving a
    per-slot vocab mask.

    The host advances the machine at each emit boundary with the token
    just emitted; :meth:`mask` renders the set of now-legal next
    tokens as a logit-bias row (0 allowed, :data:`MASK_OFF` not) that
    the engine applies IN-PROGRAM on the next step — the mask is a
    traced operand of the same compiled programs, so constrained
    decoding costs zero extra dispatches.  Because the mask can change
    every token, a constrained slot pins the batcher to the per-step
    decode path (``ContinuousBatcher._burst_ready``): a k-step burst
    could not observe mid-burst mask updates.

    ``token_strs`` maps token id → string; the default serving mapping
    is byte-level (``chr(id)``).  Multi-character tokens are allowed
    when every character advances the automaton.  The grammar requires
    a top-level object or array (the JSON-mode contract), after which
    :attr:`done` flips and the batcher finishes the request."""

    _WS = " \t\n\r"
    _DIGITS = "0123456789"
    # string-interior chars allowed without escaping (printable ASCII
    # minus '"' and '\\'); enough for byte-level serving vocabularies
    _STR_OK = "".join(chr(c) for c in range(0x20, 0x7F)
                      if chr(c) not in '"\\')

    def __init__(self, token_strs):
        self._toks = [str(s) for s in token_strs]
        # state: (mode, stack, literal-remainder); modes are short
        # strings, the stack holds 'O'/'A' container contexts
        self._state = ("value", (), "")

    # -- pure transition core -------------------------------------------
    @classmethod
    def _feed(cls, state, ch):
        """One character; returns the next state or None (illegal)."""
        mode, stack, lit = state
        if mode == "done":
            return None
        if mode == "str" or mode == "str_esc":
            if mode == "str_esc":
                return ("str", stack, "") if ch in '"\\/bfnrt' else None
            if ch == '"':
                return cls._after_value(stack)
            if ch == "\\":
                return ("str_esc", stack, "")
            return ("str", stack, "") if ch in cls._STR_OK else None
        if mode == "lit":
            if lit and ch == lit[0]:
                rest = lit[1:]
                return ("lit", stack, rest) if rest \
                    else cls._after_value(stack)
            return None
        if mode == "num":
            if ch in cls._DIGITS:
                return ("num", stack, "")
            if ch in ".eE+-":        # permissive; parseability is the
                return ("num", stack, "")   # test's oracle, not ours
            # a number is ended by its terminator: close/comma/ws
            nxt = cls._after_value(stack)
            return cls._feed(nxt, ch) if nxt is not None else None
        if mode == "key" or mode == "key_esc":
            if mode == "key_esc":
                return ("key", stack, "") if ch in '"\\/bfnrt' else None
            if ch == '"':
                return ("colon", stack, "")
            if ch == "\\":
                return ("key_esc", stack, "")
            return ("key", stack, "") if ch in cls._STR_OK else None
        if ch in cls._WS:
            return state            # whitespace is legal between tokens
        if mode == "value":
            if ch == "{":
                return ("obj_key0", stack + ("O",), "")
            if ch == "[":
                return ("arr_val0", stack + ("A",), "")
            if not stack:           # top level must be a container
                return None
            if ch == '"':
                return ("str", stack, "")
            if ch in cls._DIGITS or ch == "-":
                return ("num", stack, "")
            if ch == "t":
                return ("lit", stack, "rue")
            if ch == "f":
                return ("lit", stack, "alse")
            if ch == "n":
                return ("lit", stack, "ull")
            return None
        if mode in ("obj_key0", "obj_key"):
            if ch == '"':
                return ("key", stack, "")
            if ch == "}" and mode == "obj_key0":
                return cls._after_value(stack[:-1])
            return None
        if mode == "colon":
            return ("value", stack, "") if ch == ":" else None
        if mode == "arr_val0":
            if ch == "]":
                return cls._after_value(stack[:-1])
            nxt = cls._feed(("value", stack, ""), ch)
            return nxt
        if mode == "obj_next":
            if ch == ",":
                return ("obj_key", stack, "")
            if ch == "}":
                return cls._after_value(stack[:-1])
            return None
        if mode == "arr_next":
            if ch == ",":
                return ("value", stack, "")
            if ch == "]":
                return cls._after_value(stack[:-1])
            return None
        return None

    @staticmethod
    def _after_value(stack):
        if not stack:
            return ("done", (), "")
        return ("obj_next" if stack[-1] == "O" else "arr_next",
                stack, "")

    @classmethod
    def _close_cost(cls, state):
        """Minimal characters from ``state`` to ``done`` — the cost of
        closing every open string/literal/number and container by the
        shortest legal path (a mandatory value costs 1: a digit)."""
        mode, stack, lit = state
        d = len(stack)
        if mode == "done":
            return 0
        if mode == "value":
            return d + (1 if stack else 2)    # top level needs "[]"
        return d + {"num": 0, "str": 1, "str_esc": 2,
                    "lit": len(lit), "key": 3, "key_esc": 4,
                    "colon": 2, "obj_key": 4, "obj_key0": 0,
                    "arr_val0": 0, "obj_next": 0, "arr_next": 0}[mode]

    def _feed_token(self, state, tok: int):
        s = self._toks[tok] if 0 <= int(tok) < len(self._toks) else ""
        if not s:
            return None
        for ch in s:
            state = self._feed(state, ch)
            if state is None:
                return None
        return state

    # -- host API --------------------------------------------------------
    @property
    def done(self) -> bool:
        return self._state[0] == "done"

    def advance(self, tok: int) -> bool:
        """Consume the emitted token; False if it was not legal (the
        in-program mask makes this unreachable on the serving path)."""
        nxt = self._feed_token(self._state, int(tok))
        if nxt is None:
            return False
        self._state = nxt
        return True

    def mask(self, budget: Optional[int] = None) -> _np.ndarray:
        """Logit-bias row for the NEXT token: 0 for every token whose
        whole string advances the automaton, :data:`MASK_OFF`
        otherwise.  O(vocab × token length) host work per emitted
        token — the serving mapping is byte-level, so this is a few
        thousand character transitions at the emit boundary, never on
        the device.

        ``budget`` (tokens still emittable, INCLUDING the one this
        mask gates) additionally drops every token whose resulting
        state could not be closed within what remains — the output is
        then guaranteed to parse before the budget runs out (with
        byte-level tokens, the shortest closing path always survives
        the filter, so the mask can never go empty while
        ``_close_cost(state) <= budget``)."""
        row = _np.full(len(self._toks), MASK_OFF, _np.float32)
        if self.done:
            return row
        for t in range(len(self._toks)):
            nxt = self._feed_token(self._state, t)
            if nxt is None:
                continue
            if budget is not None and self._close_cost(nxt) \
                    > budget - len(self._toks[t]):
                continue
            row[t] = 0.0
        return row
