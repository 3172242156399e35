"""A made-up family that routes: pre-norm blocks whose feed-forward part
is four SwiGLU experts under a top-1 softmax router, as the program's
``TransformerConfig(moe_num_experts=4, moe_top_k=1)`` builds it
(``tests/unit/test_serving.py``).  The evaluation capacity factor equals
the number of experts, so no token is ever dropped and routing is a pure
function of the token."""

REFERENCE = "toy_routed"
ROUTED = True       # its reference may return (logits, decided)


def transformer_kwargs(cfg):
    experts = cfg["n_routed_experts"]
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        ffn_hidden_size=cfg["moe_intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        activation="silu", use_rmsnorm=True, use_rope=True,
        moe_num_experts=experts, moe_top_k=cfg["num_experts_per_tok"],
        moe_capacity_factor=2.0, moe_eval_capacity_factor=float(experts),
        tie_embeddings=cfg["tie_word_embeddings"])


def model_sizes(cfg, engine_cfg):
    """``Run.model`` of this family: the dense sizes the ragged kernel's
    cost reads, and what a cost of its expert layer would."""
    return {"n_layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "page_size": engine_cfg["page_size"], "kv_bytes": 2,
            "experts_held": cfg["n_routed_experts"],
            "experts_per_token": cfg["num_experts_per_tok"]}
