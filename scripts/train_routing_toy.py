"""When does a seeded ``mellum`` model's routing leave its seeded balance?

A CPU toy of ``train-mellum2-12b-ep4-s8192`` (PERF.md sections 4, 6, 7): one
period of three window layers and a YaRN full layer at width ``--hidden``, 16
of 64 softmax-routed experts held, uniform random tokens, plain Adam in
float32 on ``CausalTransformerLM.loss(counted=True)``.  A step prints
nothing; the end prints the loss and the held experts' pairs, rows and
fullest load at sixteen steps of the run, pairs and rows as shares of the
seeded expectation (tokens x top-k x held / experts), and the first step at
which the rows are 3 % off their first five steps' mean.

What it showed (PR 43): at embedding spread 1 the loss drops to ln V within
some ten steps once rate x steps x hidden passes about 11.5, and the held
pairs fall to 45 % (the absent experts add nothing, which that loss
prefers); the threshold goes with the square of the spread (45-55 at 2, 110
at 3, none by 173 at 4); a balance coefficient of 0.01 keeps the pairs
within 88-105 % through the drop.

    python3 scripts/train_routing_toy.py --spread 2 --lr 1.2e-3 --seed 3
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.families import mellum as family  # noqa: E402
from deepspeed_tpu.models.transformer import (  # noqa: E402
    CausalTransformerLM, TransformerConfig)

TOY = os.path.join(ROOT, "tests", "chipbench", "data", "tiny-mellum.json")
BETA1, BETA2, EPS = 0.9, 0.999, 1e-8
HELD, EXPERTS, TOP_K, LAYERS = 16, 64, 8, 4


def build(args):
    with open(TOY) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=args.hidden, head_dim=64,
               num_attention_heads=args.hidden // 64, num_key_value_heads=2,
               moe_intermediate_size=64, num_experts=HELD,
               num_experts_per_tok=TOP_K, vocab_size=args.vocab,
               sliding_window=128, max_position_embeddings=4096,
               moe_aux_loss_coef=args.aux,
               published={"num_experts": EXPERTS},
               seeded_weights={"embedding_std": args.spread})
    cfg["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=1024, factor=16, beta_fast=32,
        beta_slow=1, attention_factor=1.2772588722239782, rope_theta=500000)
    cfg["rope_parameters"]["sliding_attention"].update(rope_theta=500000)
    return CausalTransformerLM(TransformerConfig(
        **family.transformer_kwargs(cfg), remat=False,
        attn_impl="reference"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spread", type=float, default=1.0,
                    help="seeded_weights.embedding_std")
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--aux", type=float, default=0.0,
                    help="moe_aux_loss_coef")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--hidden", type=int, default=384)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()
    model = build(args)
    params = model.init(jax.random.key(args.seed))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(params, m, v, ids, t):
        (loss, counters), g = jax.value_and_grad(
            lambda p: model.loss(p, ids, counted=True), has_aux=True)(params)
        m = jax.tree.map(lambda a, b: BETA1 * a + (1 - BETA1) * b, m, g)
        v = jax.tree.map(lambda a, b: BETA2 * a + (1 - BETA2) * b * b, v, g)
        c1, c2 = 1 - BETA1 ** t, 1 - BETA2 ** t
        params = jax.tree.map(
            lambda p, a, b: p - args.lr * (a / c1) / (jnp.sqrt(b / c2) + EPS),
            params, m, v)
        return params, m, v, loss, counters

    rng = np.random.default_rng(args.seed)
    read = []
    t0 = time.time()
    for n in range(args.steps):
        ids = jnp.asarray(rng.integers(0, args.vocab, (args.rows, args.seq),
                                       dtype=np.int32))
        params, m, v, loss, counters = step(params, m, v, ids,
                                            jnp.float32(n + 1))
        read.append((float(loss), *(int(c) for c in counters)))
    seeded = args.rows * args.seq * TOP_K * LAYERS * HELD // EXPERTS
    loss, pairs, fullest, rows = (np.array(x, float) for x in zip(*read))
    pairs, rows = pairs / seeded, rows / seeded
    start = rows[:5].mean()
    off = np.abs(rows - start) > 0.03 * start
    left = next((i for i in range(5, args.steps - 2)
                 if off[i] and off[i + 2]), None)
    print(f"spread {args.spread} lr {args.lr} aux {args.aux} seed {args.seed} "
          f"hidden {args.hidden}: {time.time() - t0:.0f} s, rows 3 % off "
          f"at step {left} (rate x steps x hidden "
          f"{None if left is None else round(args.lr * left * args.hidden, 1)}"
          f"), loss {loss[:5].mean():.4f} -> {loss[-5:].mean():.4f}")
    at = range(0, args.steps, max(1, args.steps // 16))
    for name, col, digits in (("loss", loss, 3), ("pairs", pairs, 3),
                              ("rows", rows, 3), ("fullest", fullest, 0)):
        print(f"  {name:8s}", [round(float(col[i]), digits) for i in at])


if __name__ == "__main__":
    main()
