"""Kimi-K2-Instruct ``config.json`` keys (``model_type: kimi_k2``, the
DeepSeek-V3 block) -> the program's model settings: latent attention with
NO key selection (every query attends over its whole context), YaRN
rotary scaling, a leading dense layer, then ``noaux_tc`` sigmoid-routed
expert layers with one shared expert, of which this chip holds
``n_routed_experts`` of the ``published`` count (experts 0..held-1; the
router keeps its published width)."""

REFERENCE = "kimi_k2"
ROUTED = True       # its reference returns (logits, decided)


def _yarn(scaling):
    """``rope_scaling`` as the program's ``rope_yarn`` data."""
    if not scaling:
        return None
    assert scaling["type"] == "yarn", scaling
    return (float(scaling["factor"]),
            int(scaling["original_max_position_embeddings"]),
            float(scaling["beta_fast"]), float(scaling["beta_slow"]),
            float(scaling["mscale"]), float(scaling["mscale_all_dim"]))


def transformer_kwargs(cfg):
    published = cfg.get("published", {})
    assert cfg["n_group"] == cfg["topk_group"] == 1, "no group step is built"
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rope_yarn=_yarn(cfg.get("rope_scaling")),
        norm_eps=cfg["rms_norm_eps"], activation="silu", use_rmsnorm=True,
        use_rope=True, tie_embeddings=cfg["tie_word_embeddings"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_dense_layers=cfg["first_k_dense_replace"],
        moe_num_experts=published.get("n_routed_experts",
                                      cfg["n_routed_experts"]),
        moe_experts_held=cfg["n_routed_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_dropless=True,
        moe_scoring=cfg["scoring_func"],
        moe_norm_topk_prob=cfg["norm_topk_prob"],
        moe_route_norm_eps=1e-20,
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        moe_shared_experts=cfg["n_shared_experts"],
        init_embed_std=cfg.get("seeded_weights", {}).get("embedding_std"))


def model_sizes(cfg, engine_cfg):
    """``Run.model`` of this family's serving cells: what the costs of its
    layers read (``reducers/serve_mfu_dense_latent.py``)."""
    published = cfg.get("published", {})
    layers = cfg["num_hidden_layers"]
    entry = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return {
        "n_layers": layers,
        "dense_layers": cfg["first_k_dense_replace"],
        "expert_layers": layers - cfg["first_k_dense_replace"],
        "hidden": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"],
        "dense_attention": True,
        "dense_ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "shared_experts": cfg["n_shared_experts"],
        "experts_held": cfg["n_routed_experts"],
        "experts_published": published.get("n_routed_experts",
                                           cfg["n_routed_experts"]),
        "experts_per_token": cfg["num_experts_per_tok"],
        "vocab": cfg["vocab_size"],
        "page_size": engine_cfg["page_size"], "kv_bytes": 2,
        # a row of the pool: the entry in whole lane tiles of 128 values
        "entry_bytes": 2 * -(-entry // 128) * 128}
