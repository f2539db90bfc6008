"""Operations and bytes the dots3-note programs need, from shapes alone (the
configuration's keys as the file states them: this chip's share).  Kept with
the benchmark, beside `opcount.py`, so that no PR that claims a gain can change
the count.  A multiply-add is two operations.  The count is the LEAST the
algorithm needs, whatever implements it: an expert no token chose is not read;
a sliding layer reads its window's rows; a full layer reads every cached INDEX
KEY (the indexer scores them all) and the latent rows of the positions it
chose, no others — a program that reads the strip under a mask reads lower.  A
latent row is read ONCE and is key and value both."""


def kind(cfg, sliding):
    """``(H, r_q, r_kv, d_n, d_r, d_v)`` of a sliding or a full layer."""
    p = "swa_" if sliding else ""
    return (cfg[p + "num_attention_heads"], cfg[p + "q_lora_rank"],
            cfg[p + "kv_lora_rank"], cfg[p + "qk_nope_head_dim"],
            cfg[p + "qk_rope_head_dim"], cfg[p + "v_head_dim"])


def layer_kinds(cfg):
    """``(full layers, sliding layers)``."""
    n = sum(k == "sliding_attention" for k in cfg["layer_types"])
    return len(cfg["layer_types"]) - n, n


def index_params(cfg):
    """The indexer's matrices, its key's LayerNorm with them: whole on every
    chip."""
    HI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * HI * dI + cfg["hidden_size"] * (dI + HI) \
        + 2 * dI


def attention_params(cfg, sliding):
    d = cfg["hidden_size"]
    H, r_q, r, d_n, d_r, d_v = kind(cfg, sliding)
    own = d * r_q + r_q + r_q * H * (d_n + d_r) + d * (r + d_r) + r \
        + r * H * (d_n + d_v) + H * d_v * d + d * H
    return own + (0 if sliding else index_params(cfg))


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_read_params(cfg):
    """Parameters every decode step reads whatever the routing: attention and
    the two norms of every layer, the dense layers' FFN, the shared expert,
    the router and its choice bias of every expert layer, the final norm and
    the head."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    full, sliding = layer_kinds(cfg)
    dense = cfg["first_k_dense_replace"]
    P = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    return (full * attention_params(cfg, False)
            + sliding * attention_params(cfg, True) + L * 2 * d
            + dense * 3 * d * cfg["intermediate_size"]
            + (L - dense) * (expert_params(cfg) * cfg["n_shared_experts"]
                             + d * P + P)
            + d + d * cfg["vocab_size"])


def held_pairs_a_token(cfg):
    """(token, expert) pairs of a token that fall on the experts held here, a
    layer, on average: its share of the experts per token."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def active_params(cfg):
    """Parameters a token multiplies with: the always-read ones and its held
    pairs' experts."""
    moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return always_read_params(cfg) \
        + moe * held_pairs_a_token(cfg) * expert_params(cfg)


def row_features(cfg, sliding):
    """Features a cached position keeps in a layer: the latent row, and in a
    full layer the index key."""
    _, _, r, _, d_r, _ = kind(cfg, sliding)
    return r + d_r + (0 if sliding else cfg["index_head_dim"])


def kv_bytes_per_token(cfg, kv_bytes):
    full, sliding = layer_kinds(cfg)
    return (full * row_features(cfg, False)
            + sliding * row_features(cfg, True)) * kv_bytes


# -- the kernels' own calls (one layer) -------------------------------------

def latent_decode_call(cfg, live_slots, window_tokens, kv_bytes):
    """The paged latent decode of a sliding layer: ``H`` absorbed queries a
    slot over ``window_tokens`` rows, each read once: ``(flops, bytes)``."""
    H, _, r, _, d_r, _ = kind(cfg, True)
    keys = live_slots * window_tokens
    return (2.0 * H * (r + d_r) * keys + 2.0 * H * r * keys,
            (r + d_r) * kv_bytes * keys
            + live_slots * H * ((r + d_r) * kv_bytes + r * 4))


def index_select_call(cfg, live_slots, context_tokens, kv_bytes):
    """The indexer of a full layer: ``index_n_heads`` queries a slot against
    every cached index key, ReLU, weights and sum over heads; the choice
    itself moves only the scores."""
    HI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
    keys = live_slots * context_tokens
    return ((2.0 * dI + 3.0) * HI * keys,
            dI * kv_bytes * keys + 4 * keys
            + live_slots * HI * (dI * kv_bytes + 4))


def sparse_latent_call(cfg, live_slots, selected_tokens, kv_bytes):
    """The latent attention of a full layer over the chosen rows."""
    H, _, r, _, d_r, _ = kind(cfg, False)
    keys = live_slots * selected_tokens
    return (2.0 * H * (r + d_r) * keys + 2.0 * H * r * keys,
            (r + d_r) * kv_bytes * keys + 4 * keys
            + live_slots * H * ((r + d_r) * kv_bytes + r * 4))


# -- the programs -------------------------------------------------------------

def decode_step(cfg, live_slots, experts_touched, context_tokens,
                window_tokens, selected_tokens, param_bytes, kv_bytes):
    """One decode step: ``live_slots`` streams, ``experts_touched`` routed
    experts read (summed over the expert layers); a live slot has
    ``context_tokens`` written positions, of which a sliding layer reads
    ``window_tokens`` and a full layer scores all and reads
    ``selected_tokens``: ``(flops, bytes)``."""
    full, sliding = layer_kinds(cfg)
    flops = 2.0 * active_params(cfg) * live_slots
    nbytes = (always_read_params(cfg)
              + experts_touched * expert_params(cfg)) * param_bytes \
        + kv_bytes_per_token(cfg, kv_bytes) * live_slots       # the writes
    for n, calls in ((sliding, (latent_decode_call(
            cfg, live_slots, window_tokens, kv_bytes),)),
            (full, (index_select_call(cfg, live_slots, context_tokens,
                                      kv_bytes),
                    sparse_latent_call(cfg, live_slots, selected_tokens,
                                       kv_bytes)))):
        for f, b in calls:
            flops += n * f
            nbytes += n * b
    return flops, nbytes


def prefill_call(cfg, positions, context, held_experts, param_bytes,
                 kv_bytes):
    """One prefill call that computes ``positions`` positions after
    ``context`` cached ones (a hit's suffix, a chunk of a miss), with
    ``held_experts`` routed experts read (all of them once a call has more
    tokens than experts).  Attention in its cheaper, unabsorbed form: every
    row a query may read expanded to its heads' keys and values once, then a
    head's product a (query, key) pair — over the window of a sliding layer,
    over the chosen ``min(index_topk, keys)`` of a full one, whose index
    scores every (query, earlier key) pair."""
    full, sliding = layer_kinds(cfg)
    keys = context + positions
    pairs_all = positions * (context + (positions + 1) / 2.0)
    flops = 2.0 * active_params(cfg) * positions
    nbytes = (always_read_params(cfg)
              + held_experts * expert_params(cfg)) * param_bytes \
        + kv_bytes_per_token(cfg, kv_bytes) * positions
    for n, is_sliding in ((full, False), (sliding, True)):
        H, _, r, d_n, d_r, d_v = kind(cfg, is_sliding)
        if is_sliding:
            reach = min(cfg["sliding_window_size"], keys)
            read = min(keys, positions + reach)
        else:
            reach = min(cfg["index_topk"], keys)
            read = keys
            HI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
            flops += n * (2.0 * dI + 3.0) * HI * pairs_all
            nbytes += n * dI * kv_bytes * context
        pairs = min(pairs_all, positions * reach)
        flops += n * (2.0 * r * H * (d_n + d_v) * read
                      + 2.0 * H * (d_n + d_r + d_v) * pairs)
        nbytes += n * (r + d_r) * kv_bytes * max(read - positions, 0)
    return flops, nbytes
