"""Operations and bytes the SmallThinker decode step needs, from shapes alone
(the configuration's keys as the file states them).  Kept with the benchmark,
beside `opcount.py`, so that no PR that claims a gain can change the count.  A
multiply-add is two operations.  The count is the LEAST the algorithm needs:
an expert no token chose is not read, a key behind a window is not read — a
program that reads all of either reads lower."""


def _attention_params(cfg):
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * hq * D + 2 * d * hkv * D                # q, o; k, v


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def _published(cfg):
    return cfg.get("moe_num_primary_experts_published",
                   cfg["moe_num_primary_experts"])


def always_read_params(cfg):
    """Parameters every decode step reads whatever the routing: attention,
    the router and the two norms of every layer, the final norm, and the
    head.  (Of the embedding a step reads one row a slot, counted with the
    activations: nothing.)"""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    layer = _attention_params(cfg) + d * _published(cfg) + 2 * d
    return L * layer + d + d * cfg["vocab_size"]


def keys_read(cfg, context_tokens, window_tokens):
    """Keys one live slot reads in a step, summed over the layers:
    ``context_tokens`` written positions in a full layer, ``window_tokens``
    (the mean over the slots of min(written, window), NOT min of the mean)
    in a windowed one."""
    windowed = sum(1 for s in cfg["sliding_window_layout"] if s)
    return (cfg["num_hidden_layers"] - windowed) * context_tokens \
        + windowed * window_tokens


def paged_gqa_call(cfg, live_slots, keys, kv_bytes):
    """One layer's paged attention call over ``keys`` keys a live slot:
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


def decode_step(cfg, live_slots, experts_touched, context_tokens,
                window_tokens, param_bytes, kv_bytes):
    """One decode step: ``live_slots`` streams, ``experts_touched`` routed
    experts read (summed over the layers), ``context_tokens`` written
    positions a live slot and ``window_tokens`` of them inside a windowed
    layer's window: ``(flops, bytes)``."""
    D = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    L = cfg["num_hidden_layers"]
    keys = keys_read(cfg, context_tokens, window_tokens) * live_slots
    held_share = cfg["moe_num_primary_experts"] / _published(cfg)
    active = always_read_params(cfg) + L * held_share \
        * cfg["moe_num_active_primary_experts"] * expert_params(cfg)
    flops = 2.0 * active * live_slots + 4.0 * hq * D * keys     # q.k, p.v
    nbytes = (always_read_params(cfg)
              + experts_touched * expert_params(cfg)) * param_bytes \
        + 2 * hkv * D * kv_bytes * (keys + L * live_slots)
    return flops, nbytes
