"""GPT-NeoX (Pythia) ``config.json`` keys -> the program's model settings.

The same mapping ``module_inject/policies.py:GPTNeoXPolicy.build`` makes
for a converted checkpoint, so the benchmark runs the model a user of the
repo gets.  The policy maps ``hidden_act: "gelu"`` (erf in the published
model) to the program's ``"gelu"`` (tanh form); the reference keeps erf.
"""

REFERENCE = "gpt_neox"


def transformer_kwargs(cfg):
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    rotary = int(head_dim * cfg["rotary_pct"])
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rotary_emb_base"]),
        norm_eps=cfg["layer_norm_eps"], activation="gelu",
        use_rmsnorm=False, use_rope=True,
        rope_dim=None if rotary == head_dim else rotary,
        parallel_block=cfg["use_parallel_residual"], use_bias=True,
        norm_bias=True, tie_embeddings=cfg["tie_word_embeddings"])
