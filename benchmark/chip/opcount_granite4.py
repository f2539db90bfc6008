"""Operations and bytes the Granite 4.0-H programs need, from shapes alone (the
configuration's keys as the file states them: nothing is the chip's share, the
model is whole).  Kept with the benchmark, beside `opcount.py`, so that no PR
that claims a gain can change the count.  A multiply-add is two operations.
The count is the LEAST the algorithm needs: every weight read once a step (the
tied embedding once, as the head), a live sequence's state read once and
written once a token, the recurrence counted token by token (a decay, an outer
product and ``S C``: five operations an element of the state), not by what a
chunked form spends on top."""


def layer_kinds(cfg):
    """``(Mamba-2 layers, attention layers)``."""
    n = sum(kind == "mamba" for kind in cfg["layer_types"])
    return n, cfg["num_hidden_layers"] - n


def _mamba_dims(cfg):
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return H, P, N, H * P, H * P + 2 * N


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matrix_elements(cfg):
    """One sequence's matrices in one Mamba layer: heads x P x N."""
    H, P, N, _, _ = _mamba_dims(cfg)
    return H * P * N


def state_elements(cfg):
    """What one sequence keeps in one Mamba layer: the matrices and the
    convolution's tail."""
    _, _, _, _, conv = _mamba_dims(cfg)
    return matrix_elements(cfg) + (cfg["mamba_d_conv"] - 1) * conv


def state_bytes_a_sequence(cfg, state_bytes=4):
    """A sequence's state over all the Mamba layers, whatever its context."""
    return layer_kinds(cfg)[0] * state_elements(cfg) * state_bytes


def kv_bytes_a_token(cfg, kv_bytes):
    """Keys and values a cached position keeps over the attention layers."""
    return layer_kinds(cfg)[1] * 2 * cfg["num_key_value_heads"] \
        * head_dim(cfg) * kv_bytes


def mamba_params(cfg):
    """A Mamba-2 mixer's own: W_in, the taps and their bias, dt_bias, A_log,
    D, the inner norm, W_out."""
    d = cfg["hidden_size"]
    H, _, _, inner, conv = _mamba_dims(cfg)
    return d * (inner + conv + H) + cfg["mamba_d_conv"] * conv + conv \
        + 3 * H + inner + inner * d


def attention_params(cfg):
    """An attention mixer's own: W_q, W_k, W_v, W_o."""
    d, D = cfg["hidden_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * hq * D + 2 * d * hkv * D


def layer_common_params(cfg):
    """What every layer has beside its mixer: the MLP and two norms."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_intermediate_size"] + 2 * d


def body_params(cfg):
    """Parameters every position reads, without the head."""
    n_mamba, n_attn = layer_kinds(cfg)
    return n_mamba * mamba_params(cfg) + n_attn * attention_params(cfg) \
        + cfg["num_hidden_layers"] * layer_common_params(cfg)


def head_params(cfg):
    """The final norm and the head, which IS the embedding (held once)."""
    return cfg["hidden_size"] + cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg):
    return body_params(cfg) + head_params(cfg)


def ssd_step_call(cfg, live_slots, state_bytes=4):
    """One Mamba layer's one-token step over ``live_slots`` rows (the
    kernel's own: the matrices): five operations an element; a row read once
    and written once; x, the decay, B, C in and y out: ``(flops, bytes)``."""
    _, _, N, inner, _ = _mamba_dims(cfg)
    flops = 5.0 * matrix_elements(cfg) * live_slots
    nbytes = (2 * matrix_elements(cfg) * state_bytes
              + (3 * inner + 2 * N) * 4) * live_slots
    return flops, nbytes


def ssd_prefill_call(cfg, positions, calls, act_bytes, state_bytes=4):
    """One Mamba layer's recurrence over ``positions`` prompt positions in
    ``calls`` prompts: the same five operations an element a position; x, B,
    C, the two gates in and y out a position, the matrices in and out a
    prompt: ``(flops, bytes)``."""
    H, _, _, inner, conv = _mamba_dims(cfg)
    flops = 5.0 * matrix_elements(cfg) * positions
    nbytes = positions * (conv * act_bytes + 2 * H * 4 + inner * 4) \
        + 2 * calls * matrix_elements(cfg) * state_bytes
    return flops, nbytes


def paged_gqa_call(cfg, live_slots, keys, kv_bytes):
    """One attention layer's paged call over ``keys`` keys a live slot:
    ``(flops, bytes)`` — q.k and p.v for every query head; each KV head's
    keys and values read once, the queries and outputs beside them."""
    D = head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = 4.0 * hq * D * keys * live_slots
    nbytes = 2 * hkv * D * kv_bytes * keys * live_slots \
        + 2 * hq * D * 4 * live_slots                      # q in, o out: f32
    return flops, nbytes


def decode_step(cfg, live_slots, context_tokens, param_bytes, kv_bytes,
                state_bytes=4):
    """One decode step: ``live_slots`` streams, ``context_tokens`` written
    positions a live slot behind the step: ``(flops, bytes)`` — every weight
    once + each live slot's state (matrices and tail) read and written in
    every Mamba layer + the keys and values read and the new ones written in
    every attention layer."""
    n_mamba, n_attn = layer_kinds(cfg)
    a_flops, a_bytes = paged_gqa_call(cfg, live_slots, context_tokens,
                                      kv_bytes)
    s_flops, _ = ssd_step_call(cfg, live_slots, state_bytes)
    flops = 2.0 * total_params(cfg) * live_slots + n_attn * a_flops \
        + n_mamba * s_flops
    nbytes = total_params(cfg) * param_bytes \
        + 2 * state_bytes_a_sequence(cfg, state_bytes) * live_slots \
        + n_attn * a_bytes + kv_bytes_a_token(cfg, kv_bytes) * live_slots
    return flops, nbytes


def prefill_call(cfg, positions, context_tokens, param_bytes, kv_bytes,
                 state_bytes=4):
    """One prefill call over ``positions`` prompt positions that follow
    ``context_tokens`` cached ones (0 on a miss): ``(flops, bytes)``.
    Position j of an attention layer reads ``context + j + 1`` keys; the
    head runs over one row; the weights are read once; the state comes in
    and goes out once."""
    n_mamba, n_attn = layer_kinds(cfg)
    D, hq = head_dim(cfg), cfg["num_attention_heads"]
    keys = positions * context_tokens + positions * (positions + 1) / 2.0
    s_flops, s_bytes = ssd_prefill_call(cfg, positions, 1, param_bytes,
                                        state_bytes)
    flops = 2.0 * body_params(cfg) * positions + 2.0 * head_params(cfg) \
        + n_attn * 4.0 * hq * D * keys + n_mamba * s_flops
    nbytes = total_params(cfg) * param_bytes + n_mamba * s_bytes \
        + kv_bytes_a_token(cfg, kv_bytes) * (context_tokens + 2 * positions)
    return flops, nbytes
