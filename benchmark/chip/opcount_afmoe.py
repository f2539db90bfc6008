"""Operations and bytes the AFMoE decode step needs, from shapes alone (the
configuration's keys as the file states them: this chip's share).  Kept with
the benchmark, beside `opcount.py`, so that no PR that claims a gain can change
the count.  A multiply-add is two operations.  The count is the LEAST the
algorithm needs: an expert no token chose is not read, a key behind a window
is not read — a program that reads all of either reads lower."""


def _attention_params(cfg):
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 3 * d * hq * D + 2 * d * hkv * D            # q, gate, o; k, v


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_read_params(cfg):
    """Parameters every decode step reads whatever the routing: attention of
    every layer, the dense layers' FFN, the shared expert and the router of
    every expert layer, the norms, and the head.  (Of the embedding a step
    reads one row a slot, counted with the activations: nothing.)"""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = cfg["num_dense_layers"]
    moe = L - dense
    shared = expert_params(cfg) * cfg["num_shared_experts"]
    published = cfg.get("num_experts_published", cfg["num_experts"])
    router = d * published + published            # and the choice's bias
    norms = L * (4 * d + 2 * cfg["head_dim"]) + d
    return (L * _attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + moe * (shared + router) + norms + d * cfg["vocab_size"])


def window_bounded_context(cfg, context_tokens):
    """Keys one slot with ``context_tokens`` written positions reads in a
    step, summed over the layers: all of them in a full layer, the window's
    worth in a sliding one."""
    w = cfg["sliding_window"]
    return sum(min(context_tokens, w) if kind == "sliding_attention"
               else context_tokens for kind in cfg["layer_types"])


def afmoe_decode_step(cfg, live_slots, experts_touched, context_tokens,
                      param_bytes, kv_bytes):
    """One decode step: ``live_slots`` streams, ``experts_touched`` routed
    experts read (summed over the expert layers), ``context_tokens`` written
    positions a live slot: ``(flops, bytes)``."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    moe = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    keys = window_bounded_context(cfg, context_tokens) * live_slots
    # every live token: the always-read matrices, and its share of the
    # experts per token that are held here (pairs held = touched rows)
    held_share = cfg["num_experts"] / cfg.get("num_experts_published",
                                              cfg["num_experts"])
    active = always_read_params(cfg) \
        + moe * cfg["num_experts_per_tok"] * held_share * expert_params(cfg)
    flops = 2.0 * active * live_slots + 4.0 * hq * D * keys     # q.k, p.v
    nbytes = (always_read_params(cfg)
              + experts_touched * expert_params(cfg)) * param_bytes \
        + 2 * hkv * D * kv_bytes * (keys + cfg["num_hidden_layers"]
                                    * live_slots)
    return flops, nbytes
