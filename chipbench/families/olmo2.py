"""OLMo-2 ``config.json`` keys -> the program's model settings, as
``module_inject/policies.py:Olmo2Policy.build`` maps them."""

REFERENCE = "olmo2"


def transformer_kwargs(cfg):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=heads,
        n_kv_heads=None if kv == heads else kv,
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        activation="silu", use_rmsnorm=True, use_rope=True,
        qk_norm="rms_flat", post_norm_only=True,
        tie_embeddings=cfg["tie_word_embeddings"])
