"""Granite 4.0-H ``config.json`` keys (``model_type: granitemoehybrid``)
-> the program's model settings: ``layer_types`` as the per-layer pattern
of mixers (``mamba``: a Mamba-2 state-space layer; ``attention``:
grouped-query attention that carries no positions), scanned by the
pattern's period; the four scalar multipliers; a SwiGLU of
``shared_intermediate_size`` in every layer (no routed experts:
``num_local_experts`` 0)."""

REFERENCE = "granitemoehybrid"


def _period(kinds):
    """The shortest period the pattern of layer kinds repeats with."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def transformer_kwargs(cfg):
    kinds = list(cfg["layer_types"])
    heads, head_dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    assert len(kinds) == cfg["num_hidden_layers"] and \
        set(kinds) <= {"mamba", "attention"}
    assert cfg["num_local_experts"] == 0 and \
        cfg["position_embedding_type"] == "nope" and \
        cfg["normalization_function"] == "rmsnorm"
    assert heads * head_dim == cfg["mamba_expand"] * cfg["hidden_size"]
    assert cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"] and \
        not cfg["attention_bias"]
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden_size=cfg["shared_intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        norm_eps=cfg["rms_norm_eps"], activation=cfg["hidden_act"],
        use_rmsnorm=True, tie_embeddings=cfg["tie_word_embeddings"],
        # no positional embedding of any kind: rotary on no layer
        use_rope=True, rope_pattern=(False,) * len(kinds),
        embed_scale=float(cfg["embedding_multiplier"]),
        attn_scale=float(cfg["attention_multiplier"]),
        residual_scale=float(cfg["residual_multiplier"]),
        final_logit_scale=1.0 / float(cfg["logits_scaling"]),
        layer_period=_period(kinds),
        ssm_pattern=tuple(kind == "mamba" for kind in kinds),
        ssm_heads=heads, ssm_head_dim=head_dim,
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"])


def model_sizes(cfg, engine_cfg):
    """``Run.model`` of this family's serving cells: what the costs of its
    layers read (``reducers/serve_mfu_hybrid.py``,
    ``reducers/decode_hbm_hybrid.py``, ``costs/ragged_hybrid_serve.py``).
    The recurrent state is float32, the convolution's tail and the pages
    bf16 (the configuration's ``serve`` group)."""
    layers = cfg["num_hidden_layers"]
    ssm_layers = sum(kind == "mamba" for kind in cfg["layer_types"])
    heads = cfg["num_attention_heads"]
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {
        "n_layers": layers, "attn_layers": layers - ssm_layers,
        "ssm_layers": ssm_layers, "hidden": cfg["hidden_size"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "ffn": cfg["shared_intermediate_size"], "vocab": cfg["vocab_size"],
        "ssm_heads": cfg["mamba_n_heads"], "ssm_head_dim": cfg["mamba_d_head"],
        "ssm_inner": inner, "ssm_state": cfg["mamba_d_state"],
        "ssm_conv_dim": conv, "ssm_conv": cfg["mamba_d_conv"],
        # bytes a slot and state-space layer: the state in float32, the
        # last ``mamba_d_conv - 1`` inputs of the convolution in bf16
        "state_bytes": inner * cfg["mamba_d_state"] * 4,
        "tail_bytes": (cfg["mamba_d_conv"] - 1) * conv * 2,
        "page_size": engine_cfg["page_size"], "kv_bytes": 2}
