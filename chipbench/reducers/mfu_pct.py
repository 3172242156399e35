"""Model FLOP/s utilisation: 6 N operations a token (N as held after
``reduced``, both embeddings counted, recomputation not counted) times
tokens per second per chip, over the chip's bf16 peak."""


def read(run):
    busy = sum(s["t1"] - s["t0"] for s in run.steps)
    if not busy:
        return None
    tokens_per_chip = sum(s["tokens"] for s in run.steps) / busy / run.chips
    return 100.0 * 6 * run.model["n_params"] * tokens_per_chip \
        / run.peaks["bf16_flops_per_s"]
