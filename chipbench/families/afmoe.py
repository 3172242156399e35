"""Trinity-Mini ``config.json`` keys (``model_type: afmoe``) -> the
program's model settings: ``layer_types`` as the per-layer window and
rotary patterns (a ``full_attention`` layer carries no positions), scanned
by its period after the leading dense layers' period; gated grouped-query
attention with per-head q/k RMSNorm and sandwich norms; sigmoid-routed
expert layers of which this chip holds ``num_experts`` of the
``published`` count (experts 0..held-1; the router keeps its published
width)."""

import math

REFERENCE = "afmoe"
ROUTED = True       # its reference returns (logits, decided)


def transformer_kwargs(cfg):
    published = cfg.get("published", {})
    sliding = [kind == "sliding_attention" for kind in cfg["layer_types"]]
    assert len(sliding) == cfg["num_hidden_layers"] and \
        cfg["score_func"] == "sigmoid" and cfg["rope_scaling"] is None
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim_override=cfg["head_dim"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        activation="silu", use_rmsnorm=True, use_rope=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        qk_norm="rms", attn_gate=True, sandwich_norm=True,
        embed_scale=(math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"]
                     else None),
        local_attn_pattern=tuple(cfg["sliding_window"] if s else 0
                                 for s in sliding),
        rope_pattern=tuple(sliding),
        layer_period=cfg["global_attn_every_n_layers"],
        first_dense_layers=cfg["num_dense_layers"],
        moe_num_experts=published.get("num_experts", cfg["num_experts"]),
        moe_experts_held=cfg["num_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_dropless=True,
        moe_scoring=cfg["score_func"], moe_norm_topk_prob=cfg["route_norm"],
        moe_route_norm_eps=1e-20,
        moe_routed_scale=float(cfg["route_scale"]),
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        moe_shared_experts=cfg["num_shared_experts"],
        init_embed_std=cfg.get("seeded_weights", {}).get("embedding_std"))


def model_sizes(cfg, engine_cfg):
    """``Run.model`` of this family's serving cells: what the costs of its
    layers read (``reducers/serve_mfu_window.py``,
    ``costs/ragged_window_serve.py``)."""
    layers = cfg["num_hidden_layers"]
    window_layers = sum(kind == "sliding_attention"
                        for kind in cfg["layer_types"])
    return {
        "n_layers": layers, "window_layers": window_layers,
        "full_layers": layers - window_layers,
        "window": cfg["sliding_window"],
        "dense_layers": cfg["num_dense_layers"],
        "expert_layers": layers - cfg["num_dense_layers"],
        "hidden": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "dense_ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "shared_experts": cfg["num_shared_experts"],
        "experts_held": cfg["num_experts"],
        "experts_published": cfg.get("published", {}).get(
            "num_experts", cfg["num_experts"]),
        "experts_per_token": cfg["num_experts_per_tok"],
        "vocab": cfg["vocab_size"],
        "page_size": engine_cfg["page_size"], "kv_bytes": 2}
