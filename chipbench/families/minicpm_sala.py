"""MiniCPM-SALA ``config.json`` keys (``model_type: minicpm_sala``) -> the
program's model settings: ``mixer_types`` as the per-layer pattern of
mixers (``lightning-attn``: a linear-attention layer with a constant decay
a head and a matrix state a slot; ``minicpm4``: block-sparse grouped-query
attention over a compressed-key cache, its sizes the file's
``sparse_config``), the held layers ONE listed period; per-head q/k
RMSNorm in both kinds, rotary in the linear layers alone, a sigmoid output
gate in both; MiniCPM's muP multipliers, the residual one reckoned from
the PUBLISHED depth whatever the file holds of it.  A program without the
two mixers is refused here, at once."""

import math

REFERENCE = "minicpm_sala"
KINDS = ("minicpm4", "lightning-attn")


def published_depth(cfg):
    return int(cfg.get("published", {}).get("num_hidden_layers",
                                            cfg["num_hidden_layers"]))


def transformer_kwargs(cfg):
    from deepspeed_tpu.models.transformer import TransformerConfig
    if not hasattr(TransformerConfig, "lin_pattern"):
        raise SystemExit(
            "chipbench: this program has no linear-attention layers and no "
            "block-sparse attention (models/transformer.py has no "
            "lin_pattern / sparse): it cannot run `minicpm_sala`")
    from deepspeed_tpu.ops.block_sparse_attention import SparseSizes
    kinds = list(cfg["mixer_types"])
    sparse, seeded = cfg["sparse_config"], cfg.get("seeded_weights", {})
    assert len(kinds) == cfg["num_hidden_layers"] and set(kinds) <= set(KINDS)
    assert cfg["qk_norm"] and not cfg["attn_use_rope"] and \
        cfg["lightning_use_rope"] and cfg["use_output_gate"] and \
        cfg["use_output_norm"] and cfg["attn_use_output_gate"] and \
        cfg["lightning_nkv"] == cfg["lightning_nh"] and \
        cfg["lightning_scale"] == "1/sqrt(d)" and \
        not cfg["attention_bias"] and cfg["hidden_act"] == "silu"
    linear = tuple(kind == "lightning-attn" for kind in kinds)
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim_override=cfg["head_dim"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        activation="silu", use_rmsnorm=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        qk_norm="rms", attn_gate=True,
        # rotary in the linear layers, none in the sparse ones
        use_rope=True, rope_pattern=linear,
        embed_scale=float(cfg["scale_emb"]),
        residual_scale=float(cfg["scale_depth"])
        / math.sqrt(published_depth(cfg)),
        final_logit_scale=cfg["dim_model_base"] / cfg["hidden_size"],
        layer_period=len(kinds),        # the held slice, one listed period
        lin_pattern=linear, lin_heads=cfg["lightning_nh"],
        lin_head_dim=cfg["lightning_head_dim"],
        sparse=SparseSizes(
            block=sparse["block_size"], topk=sparse["topk"],
            kernel=sparse["kernel_size"], stride=sparse["kernel_stride"],
            init_blocks=sparse["init_blocks"], window=sparse["window_size"],
            dense_len=sparse["dense_len"]),
        init_embed_std=seeded.get("embedding_std"),
        init_head_std=seeded.get("head_std"))


def model_sizes(cfg, engine_cfg):
    """``Run.model`` of this family's serving cells: what the costs of its
    layers read (``reducers/serve_mfu_sala.py``,
    ``reducers/decode_hbm_sala.py``, ``costs/ragged_sparse_serve.py``).
    The linear state is float32; pages and compressed keys bf16 (the
    configuration's ``serve`` group)."""
    kinds = list(cfg["mixer_types"])
    sparse = cfg["sparse_config"]
    lin_inner = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return {
        "n_layers": len(kinds),
        "sparse_layers": kinds.count("minicpm4"),
        "lin_layers": kinds.count("lightning-attn"),
        "hidden": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "lin_heads": cfg["lightning_nh"],
        "lin_head_dim": cfg["lightning_head_dim"], "lin_inner": lin_inner,
        "block": sparse["block_size"], "topk": sparse["topk"],
        "kernel": sparse["kernel_size"], "stride": sparse["kernel_stride"],
        "dense_len": sparse["dense_len"],
        # bytes a slot and linear layer: the matrix state in float32
        "state_bytes": lin_inner * cfg["lightning_head_dim"] * 4,
        "page_size": engine_cfg["page_size"], "kv_bytes": 2}
