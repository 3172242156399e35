"""Granite 4.0-H ``config.json`` keys (``model_type: granitemoehybrid``)
with ``num_local_experts`` > 0 -> the program's model settings: what
``families/granitemoehybrid.py`` makes of the mixers, the pattern and the
multipliers, and in EVERY layer, in the dense SwiGLU's place, a
softmax-routed dropless expert layer (``num_experts_per_tok`` of the
published ``num_local_experts`` a token, the chosen ones' softmax scores
renormalised: the family's softmax over the top-k logits; experts of width
``intermediate_size``) of which this chip holds ``num_local_experts``
(experts 0..held-1; the router keeps its published width), beside ONE
ungated shared SwiGLU of ``shared_intermediate_size``.  These models have
128 state heads where the dense ones have 64, and a program whose prefill
writes a slot's state back into the pool as [H, P, N] copies the whole
pool at that width (``ops/ssm.py write_slot_state`` says why): such a
program is refused here, at once."""

from chipbench.families import granitemoehybrid as dense

REFERENCE = "granitemoehybrid_routed"
ROUTED = True       # its reference returns (logits, decided)


def transformer_kwargs(cfg):
    from deepspeed_tpu.ops import ssm
    if not hasattr(ssm, "write_slot_state"):
        raise SystemExit(
            "chipbench: this program's prefill puts a slot's state back "
            "into the pool as [H, P, N] (ops/ssm.py has no "
            "write_slot_state): at the 128 state heads of a routed "
            "`granitemoehybrid` its 64-row prefill copies the whole state "
            "pool on the way in and out")
    published = cfg.get("published", {})
    width = cfg["intermediate_size"]
    # the program's shared expert is a whole number of expert widths wide
    # (one SwiGLU of that width, ``moe_shared_experts`` x ``width``)
    shared, rest = divmod(cfg["shared_intermediate_size"], width)
    assert cfg["num_local_experts"] > 0 and shared > 0 and rest == 0
    kwargs = dense.transformer_kwargs(dict(cfg, num_local_experts=0))
    kwargs.update(
        moe_num_experts=published.get("num_local_experts",
                                      cfg["num_local_experts"]),
        moe_experts_held=cfg["num_local_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_dropless=True,
        moe_scoring="softmax", moe_norm_topk_prob=True,
        moe_ffn_hidden_size=width, moe_shared_experts=shared,
        init_embed_std=cfg.get("seeded_weights", {}).get("embedding_std"))
    return kwargs


def model_sizes(cfg, engine_cfg):
    """``Run.model`` of this family's serving cells: the dense family's
    keys (its ``ffn`` is the shared SwiGLU's width here) and the expert
    layer's, which ``reducers/serve_mfu_hybrid_routed.py`` reads."""
    return dict(
        dense.model_sizes(cfg, engine_cfg),
        expert_ffn=cfg["intermediate_size"],
        shared_ffn=cfg["shared_intermediate_size"],
        experts_held=cfg["num_local_experts"],
        experts_published=cfg.get("published", {}).get(
            "num_local_experts", cfg["num_local_experts"]),
        experts_per_token=cfg["num_experts_per_tok"])
