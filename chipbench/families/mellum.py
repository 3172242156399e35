"""Mellum2 ``config.json`` keys (``model_type: mellum``) -> the program's
model settings: ``layer_types`` as the per-layer window pattern, scanned by
its period (three ``sliding_attention`` layers and a ``full_attention``
one); a rotary kind a layer kind from ``rope_parameters`` (window layers
the plain ``rope_theta``, full layers YaRN: ``rope_yarn`` on a model
without latent attention); grouped-query attention with per-head q/k
RMSNorm; every layer a softmax-routed dropless expert layer of which this
chip holds ``num_experts`` of the ``published`` count (experts
0..held-1; the router keeps its published width).  A training family: the
program's dropless expert layer has to be differentiable
(``moe/sharded_moe.py``, a ``custom_vjp``), so a program from before it
is refused here, at once, and not after the reference has run."""

import math

REFERENCE = "mellum"


def transformer_kwargs(cfg):
    from deepspeed_tpu.models import transformer
    if not hasattr(transformer, "TRAIN_COUNTERS"):
        raise SystemExit(
            "chipbench: this program's dropless expert layer has no "
            "backward pass (models/transformer.py has no TRAIN_COUNTERS): "
            "it cannot train a `mellum` configuration")
    published = cfg.get("published", {})
    sliding = [kind == "sliding_attention" for kind in cfg["layer_types"]]
    period = sliding.index(False) + 1
    ropes = cfg["rope_parameters"]
    full, window = ropes["full_attention"], ropes["sliding_attention"]
    assert len(sliding) == cfg["num_hidden_layers"] \
        and all(kind == "sparse" for kind in cfg["mlp_layer_types"]) \
        and window["rope_type"] == "default" and full["rope_type"] == "yarn" \
        and window["rope_theta"] == full["rope_theta"]
    # RopeYarn(factor, original positions, beta_fast, beta_slow, mscale,
    # mscale_all_dim): cos and sin times 0.1 mscale ln(factor) + 1, which
    # is the published ``attention_factor``; the softmax scale untouched
    mscale = (full["attention_factor"] - 1.0) \
        / (0.1 * math.log(full["factor"]))
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim_override=cfg["head_dim"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(window["rope_theta"]),
        rope_yarn=(float(full["factor"]),
                   int(full["original_max_position_embeddings"]),
                   float(full["beta_fast"]), float(full["beta_slow"]),
                   mscale, 0.0),
        norm_eps=cfg["rms_norm_eps"], activation=cfg["hidden_act"],
        use_rmsnorm=True, use_rope=True, use_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm="rms",
        local_attn_pattern=tuple(cfg["sliding_window"] if s else 0
                                 for s in sliding),
        layer_period=period,
        moe_num_experts=published.get("num_experts", cfg["num_experts"]),
        moe_experts_held=cfg["num_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_dropless=True,
        moe_scoring="softmax", moe_norm_topk_prob=cfg["norm_topk_prob"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        moe_aux_loss_coef=float(cfg.get("moe_aux_loss_coef", 0.0)),
        init_embed_std=cfg.get("seeded_weights", {}).get("embedding_std"))
