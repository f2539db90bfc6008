"""The reference's side of a served model's check: run the plain reference
over each prompt with its served tokens and read how far below the reference's
best each token's logit lies.  Imports jax (the serve runner's parent does
not use it; the child and the tests do)."""
import jax
import jax.numpy as jnp
import numpy as np

import checks


def make_gap_fn(ref, cfg, precision):
    """``(params, seq (1, T), n_prompt, n_total) -> (T,)``: at row j, the gap
    of the token at position j + 1.  ``precision == "float32"``: the token
    that is in ``seq`` (the one served).  Any other: the token that the
    reference computed at that precision puts first (the control)."""
    ref_fwd = ref.make_forward(cfg, "float32")
    low_fwd = ref.make_forward(cfg, precision) \
        if precision != "float32" else None

    def fn(params, seq, n_prompt, n_total):
        logits = ref_fwd(params, seq)[0]                    # (T, V)
        j = jnp.arange(logits.shape[0])
        if low_fwd is None:
            target = jnp.roll(seq[0], -1)
        else:
            target = jnp.argmax(low_fwd(params, seq)[0], axis=-1)
        live = (j + 1 >= n_prompt) & (j + 1 < n_total)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
        return jnp.where(live, best - got, 0.0)

    return jax.jit(fn)


def serve_numbers(ref, cfg, seed, samples, precisions, gap_fns=None):
    """``samples``: ``[{"tokens": prompt, "served": tokens}]``.  Returns
    ``{precision: checks.serve_numbers(...)}``.  The weights are made here,
    from the seed; nothing of the program's is used."""
    gap_fns = {} if gap_fns is None else gap_fns
    params = ref.init_params(cfg, seed)
    T = int(cfg["deployment"]["max_len"])
    gaps = {p: [] for p in precisions}
    for s in samples:
        seq = (list(s["tokens"]) + list(s["served"]))[:T]
        n_prompt, n_total = len(s["tokens"]), len(seq)
        padded = np.zeros((1, T), np.int32)
        padded[0, :n_total] = seq
        for p in precisions:
            if p not in gap_fns:
                gap_fns[p] = make_gap_fn(ref, cfg, p)
            g = np.asarray(gap_fns[p](params, jnp.asarray(padded), n_prompt,
                                      n_total))
            gaps[p].append(g[n_prompt - 1:n_total - 1])
    return {p: checks.serve_numbers(gaps[p]) for p in precisions}
