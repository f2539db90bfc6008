"""Operations and bytes the algorithm needs, from shapes alone.  Kept with the
benchmark so that no PR that claims a gain can change the count.

A multiply-add is two operations.  Recomputed work never counts.
"""


def _gpt_dims(cfg):
    d = cfg["n_embd"]
    return d, cfg["n_layer"], cfg.get("n_inner") or 4 * d, cfg["vocab_size"]


def gpt_matmul_params(cfg):
    """Parameters that take part in a matrix product for one token: the four
    attention projections and the two FFN matrices of every layer, and the
    tied output head."""
    d, L, f, V = _gpt_dims(cfg)
    return L * (4 * d * d + 2 * d * f) + V * d


def gpt_param_count(cfg):
    """Every parameter the decode step reads: matrices, biases, LayerNorms,
    the two embeddings (the token embedding doubles as the head)."""
    d, L, f, V = _gpt_dims(cfg)
    per_layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    return L * per_layer + V * d + cfg["n_positions"] * d + 2 * d


def gpt_kv_bytes_per_token(cfg, dtype_bytes):
    d, L, _, _ = _gpt_dims(cfg)
    return 2 * L * d * dtype_bytes


def gpt_decode_step(cfg, live_slots, live_kv_tokens, param_bytes, kv_bytes):
    """One decode step of ``live_slots`` streams that hold ``live_kv_tokens``
    cached positions together: ``(flops, bytes)``.  Every weight is read once,
    every live cached position once; a slot's new K/V is written once."""
    d, L, _, _ = _gpt_dims(cfg)
    flops = 2.0 * gpt_matmul_params(cfg) * live_slots \
        + 4.0 * L * d * live_kv_tokens            # q.k and p.v
    nbytes = gpt_param_count(cfg) * param_bytes \
        + gpt_kv_bytes_per_token(cfg, kv_bytes) * (live_kv_tokens + live_slots)
    return flops, nbytes


def bert_matmul_params(cfg):
    """As the job's program holds them: encoder matrices, the MLM transform
    and the untied MLM decoder (the pooler and NSP head see one position a
    sequence and are left out)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    f, V = cfg["intermediate_size"], cfg["vocab_size"]
    return L * (4 * d * d + 2 * d * f) + d * d + d * V


def bert_train_step(cfg, batch, seq_len):
    """Forward + backward of one step, no recompute: 6 operations a parameter
    a token for the matrix products, 12 L T^2 d a sequence for attention
    scores and context (4 T^2 d forward, twice that backward)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    tokens = batch * seq_len
    return 6.0 * bert_matmul_params(cfg) * tokens \
        + 12.0 * L * seq_len * seq_len * d * batch


def least_seconds(flops, nbytes, peaks):
    """The roofline: the least time the chip could take, and which bound."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "bandwidth")
