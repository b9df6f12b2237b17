"""Operations and bytes that GLM-5's layers need, counted from the
configuration's shapes (``configs/glm_5.json``), the traffic and the
program's counters. No number here comes from the compiler's cost analysis
or from what the program moves: the counts are the least that the work
needs, so a share of the roofline computed from them cannot pass 100 %."""


def attention_params(cfg):
    """The five latent products of one layer: q_a, q_b, kv_a, kv_b, o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return d * qr + qr * h * cfg["qk_head_dim"] \
        + d * (kr + cfg["qk_rope_head_dim"]) \
        + kr * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) \
        + h * cfg["v_head_dim"] * d


def indexer_params(cfg):
    """The indexer's three products of one layer."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * heads * dim \
        + cfg["hidden_size"] * (dim + heads)


def routed(cfg):
    """The router's width: the published number of routed experts."""
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def router_params(cfg):
    return cfg["hidden_size"] * routed(cfg)


def expert_params(cfg):
    """The three matrices of one routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["n_shared_experts"] * expert_params(cfg)


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def layer_params(cfg, dense):
    """All weights of one layer held here: attention and indexer, then the
    dense feed-forward, or the router, the shared expert and the
    ``n_routed_experts`` routed experts this chip holds."""
    rest = dense_ffn_params(cfg) if dense else (
        router_params(cfg) + shared_params(cfg)
        + cfg["n_routed_experts"] * expert_params(cfg))
    return attention_params(cfg) + indexer_params(cfg) + rest


def param_count(cfg):
    """All parameters held: the layers, the embedding and the untied head
    over the rows of the vocabulary held."""
    dense = cfg["first_k_dense_replace"]
    return dense * layer_params(cfg, True) \
        + expert_layers(cfg) * layer_params(cfg, False) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]


def expected_assignments(cfg):
    """Assignments a token gives the experts held here, a layer, where the
    router is uniform: ``num_experts_per_tok`` times the share held."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / routed(cfg)


def position_flops(cfg, context, assignments=None):
    """Forward FLOPs of one position whose query sees ``context`` positions,
    all layers, 2 a multiply-add: the latent products and the indexer's, the
    index scores over the context, attention (``q k^T`` and ``p v`` in the
    plain form) over the ``index_topk`` positions selected at most, the
    dense feed-forward or the router, the shared expert and ``assignments``
    routed experts (a layer; the expectation under a uniform router where
    none were counted)."""
    if assignments is None:
        assignments = expected_assignments(cfg)
    heads = cfg["num_attention_heads"]
    every = 2 * (attention_params(cfg) + indexer_params(cfg)) \
        + 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * context \
        + 2 * heads * (cfg["qk_head_dim"] + cfg["v_head_dim"]) \
        * min(context, cfg["index_topk"])
    dense = cfg["first_k_dense_replace"]
    return cfg["num_hidden_layers"] * every \
        + dense * 2 * dense_ffn_params(cfg) \
        + expert_layers(cfg) * 2 * (router_params(cfg) + shared_params(cfg)
                                    + assignments * expert_params(cfg))


def forward_flops(cfg, positions, context, with_head, assignments=None):
    """Forward FLOPs of ``positions`` new positions whose queries see
    ``context`` positions each on average; ``with_head`` adds the product
    with the output head over the rows held."""
    each = position_flops(cfg, context, assignments)
    if with_head:
        each += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return positions * each


def index_least_seconds(cfg, context, peaks, itemsize=2):
    """The least time one layer's indexer and selection need for one live
    stream in one decode step: its ``context`` index keys read once (bytes
    over the HBM peak) or their scores' products, ``2 x index_n_heads x
    index_head_dim`` FLOPs a position (over the bf16 peak), whichever is the
    longer."""
    nbytes = context * cfg["index_head_dim"] * itemsize
    flops = 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * context
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])


def attend_least_seconds(cfg, context, peaks, itemsize=2):
    """The least time one layer's sparse read needs for one live stream in
    one decode step: the ``min(context, index_topk)`` selected latent rows of
    ``kv_lora_rank + qk_rope_head_dim`` numbers read once, or their scores
    and weighted sum in the absorbed form, ``heads x (row + kv_lora_rank) x
    2`` FLOPs a row, whichever is the longer."""
    rows = min(context, cfg["index_topk"])
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    nbytes = rows * row * itemsize
    flops = rows * cfg["num_attention_heads"] * (row + cfg["kv_lora_rank"]) * 2
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])


def experts_least_seconds(cfg, touched, assignments, peaks, itemsize=2):
    """The least time one layer's routed experts need in one step: the
    ``touched`` held experts' three matrices read once (bytes over the HBM
    peak) or the ``assignments``' products (FLOPs over the bf16 peak),
    whichever is the longer."""
    nbytes = touched * expert_params(cfg) * itemsize
    flops = assignments * 2 * expert_params(cfg)
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
