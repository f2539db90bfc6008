"""Operations and bytes the Qwen3-Next programs need, from shapes alone (the
configuration's keys as the file states them: the head counts, the experts and
the vocabulary are this chip's share).  Kept with the benchmark, beside
`opcount.py`, so that no PR that claims a gain can change the count.  A
multiply-add is two operations.  The count is the LEAST the algorithm needs:
an expert no token chose is not read, a state is read once and written once a
token, the delta rule is counted token by token (three products on the state),
not by what a chunked form spends on top."""


def is_linear(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] != 0


def layer_kinds(cfg):
    """``(Gated DeltaNet layers, full-attention layers)``."""
    n = sum(is_linear(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return n, cfg["num_hidden_layers"] - n


def state_elements(cfg):
    """What one sequence keeps in one DeltaNet layer: a Dk x Dv matrix a
    value head and the convolution's tail."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hv * dk * dv \
        + (cfg["linear_conv_kernel_dim"] - 1) * (2 * hk * dk + hv * dv)


def linear_params(cfg):
    """A DeltaNet layer's own: W_qkvz, W_ba, the taps, W_o, dt_bias, A_log,
    the gated norm."""
    d = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    conv = 2 * hk * dk + hv * dv
    return d * (conv + hv * dv) + d * 2 * hv \
        + cfg["linear_conv_kernel_dim"] * conv + hv * dv * d + 2 * hv + dv


def attention_params(cfg):
    """A full layer's own: W_q (query and gate), W_k, W_v, W_o, two norms."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hq * 2 * D + 2 * d * hkv * D + hq * D * d + 2 * D


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _published(cfg):
    return cfg.get("num_experts_published", cfg["num_experts"])


def layer_common_params(cfg):
    """What every layer has beside its mixer: the router, the shared expert
    and its gate, two norms."""
    d = cfg["hidden_size"]
    return d * _published(cfg) + 3 * d * cfg[
        "shared_expert_intermediate_size"] + d + 2 * d


def body_params(cfg):
    """Parameters every position reads whatever the routing, without the
    head: the mixers and what every layer has beside them."""
    n_lin, n_full = layer_kinds(cfg)
    return n_lin * linear_params(cfg) + n_full * attention_params(cfg) \
        + cfg["num_hidden_layers"] * layer_common_params(cfg)


def head_params(cfg):
    return cfg["hidden_size"] + cfg["hidden_size"] * cfg["vocab_size"]


def held_pairs_a_token(cfg):
    """(token, expert) pairs a token brings to the experts held here, a
    layer, under an even router."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / _published(cfg)


def paged_gqa_call(cfg, live_slots, keys, kv_bytes):
    """The full layer's paged attention call over ``keys`` keys a live slot:
    ``(flops, bytes)`` — q.k and p.v for every query head; each KV head's
    keys and values read once, the queries and outputs beside them."""
    D = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = 4.0 * hq * D * keys * live_slots
    nbytes = 2 * hkv * D * kv_bytes * keys * live_slots \
        + 2 * hq * D * 4 * live_slots                      # q in, o out: f32
    return flops, nbytes


def experts_call(cfg, pairs, experts_touched, param_bytes):
    """One layer's grouped expert product over ``pairs`` (token, expert)
    rows that touch ``experts_touched`` experts: ``(flops, bytes)``."""
    d = cfg["hidden_size"]
    flops = 2.0 * expert_params(cfg) * pairs
    nbytes = experts_touched * expert_params(cfg) * param_bytes \
        + pairs * d * (param_bytes + 4)                    # rows in, f32 out
    return flops, nbytes


def gated_delta_step_call(cfg, live_slots, state_bytes=4):
    """One DeltaNet layer's step over ``live_slots`` state rows: S^T k,
    S^T q and the rank-one update, each 2 Dk Dv a value head; a row read
    once and written once: ``(flops, bytes)``."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    flops = 6.0 * hv * dk * dv * live_slots
    nbytes = 2 * state_elements(cfg) * state_bytes * live_slots
    return flops, nbytes


def gated_delta_prefill_call(cfg, positions, calls, act_bytes,
                             state_bytes=4):
    """One DeltaNet layer's delta rule over ``positions`` prompt positions
    in ``calls`` prompts: the same three products a position; q, k, v, the
    two gates in and the output out a position, the state in and out a
    prompt: ``(flops, bytes)``."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    flops = 6.0 * hv * dk * dv * positions
    nbytes = positions * ((2 * hk * dk + hv * dv) * act_bytes + 2 * hv * 4
                          + hv * dv * 4) \
        + 2 * calls * state_elements(cfg) * state_bytes
    return flops, nbytes


def decode_step(cfg, live_slots, experts_touched, context_tokens,
                param_bytes, kv_bytes, state_bytes=4):
    """One decode step: ``live_slots`` streams, ``experts_touched`` routed
    experts read (summed over the layers), ``context_tokens`` written
    positions a live slot behind the step in a full layer: ``(flops,
    bytes)`` — weights every token reads + an expert's matrices an expert
    touched + each live slot's state read and written in every DeltaNet
    layer + the keys and values read and the new ones written in every
    full layer."""
    n_lin, n_full = layer_kinds(cfg)
    L = cfg["num_hidden_layers"]
    always = body_params(cfg) + head_params(cfg)
    active = always + L * held_pairs_a_token(cfg) * expert_params(cfg)
    a_flops, a_bytes = paged_gqa_call(cfg, live_slots, context_tokens,
                                      kv_bytes)
    s_flops, s_bytes = gated_delta_step_call(cfg, live_slots, state_bytes)
    hkv, D = cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 2.0 * active * live_slots + n_full * a_flops + n_lin * s_flops
    nbytes = (always + experts_touched * expert_params(cfg)) * param_bytes \
        + n_lin * s_bytes \
        + n_full * (a_bytes + 2 * hkv * D * kv_bytes * live_slots)
    return flops, nbytes


def prefill_call(cfg, positions, context_tokens, experts_touched,
                 param_bytes, kv_bytes, state_bytes=4):
    """One prefill call over ``positions`` prompt positions that follow
    ``context_tokens`` cached ones (0 on a miss), ``experts_touched`` routed
    experts read (summed over the layers; all held, for a long prompt):
    ``(flops, bytes)``.  Position j of a full layer reads ``context + j +
    1`` keys; the head runs over one row; the weights are read once."""
    n_lin, n_full = layer_kinds(cfg)
    L = cfg["num_hidden_layers"]
    D, hq = cfg["head_dim"], cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    per_position = body_params(cfg) \
        + L * held_pairs_a_token(cfg) * expert_params(cfg)
    keys = positions * context_tokens + positions * (positions + 1) / 2.0
    d_flops, d_bytes = gated_delta_prefill_call(cfg, positions, 1,
                                                param_bytes, state_bytes)
    flops = 2.0 * per_position * positions + 2.0 * head_params(cfg) \
        + n_full * 4.0 * hq * D * keys + n_lin * d_flops
    nbytes = (body_params(cfg) + head_params(cfg)
              + experts_touched * expert_params(cfg)) * param_bytes \
        + n_lin * d_bytes \
        + n_full * 2 * hkv * D * kv_bytes * (context_tokens + 2 * positions)
    return flops, nbytes
