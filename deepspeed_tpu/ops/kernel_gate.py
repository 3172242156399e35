"""Mosaic compile-gate: lower + compile EVERY Pallas kernel variant.

Parity: reference ``op_builder/builder.py:112`` (``is_compatible`` probes an
op before use, surfaced by ds_report).  Our equivalent risk is Mosaic
lowering failures on the real TPU backend — interpret-mode green does NOT
imply Mosaic green (round-3 caught ALiBi/window variants only because a
journaled run happened to execute them).  This gate is compile-only (no
numerics, minutes not hours) and journals one JSON line per variant:

    python -m deepspeed_tpu.ops.kernel_gate                # default backend
    python -m deepspeed_tpu.ops.kernel_gate --json-out gate.json
    ds_report --kernel-gate                                # same, via CLI

Run it FIRST in every on-chip program.
"""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _gate(name, fn, *args):
    t0 = time.time()
    try:
        jax.jit(fn).lower(*args).compile()
        out = {"variant": name, "ok": True,
               "wall_s": round(time.time() - t0, 1)}
    except Exception as e:   # noqa: BLE001 — journal every failure mode
        out = {"variant": name, "ok": False, "error": str(e)[-600:],
               "wall_s": round(time.time() - t0, 1)}
    print(json.dumps(out), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpreter instead of Mosaic (CPU smoke "
                         "test of the gate's plumbing only — interpret "
                         "green does NOT imply Mosaic green)")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    interp = bool(args.interpret)

    from deepspeed_tpu.models.transformer import alibi_slopes
    from deepspeed_tpu.ops.pallas.decode_attention import \
        decode_attention_pallas
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention, ragged_paged_attention_rect)
    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_pallas
    from deepspeed_tpu.ops.pallas.sparse_attention import \
        sparse_attention_pallas

    B, S, H, D = 2, args.seq, 8, 64
    rng = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (B, S, H, D),
                                 jnp.bfloat16) for i in range(3))
    kg, vg = (jax.random.normal(jax.random.fold_in(rng, i), (B, S, 2, D),
                                jnp.bfloat16) for i in range(3, 5))
    slopes = alibi_slopes(H)
    rows = []

    def flash_fwd(name, **kw):
        rows.append(_gate(
            f"flash_fwd_{name}",
            lambda q, k, v: flash_attention(q, k, v, interpret=interp, **kw),
            q, k, v))

    def flash_bwd(name, kk=k, vv=v, **kw):
        def f(q, k, v):
            return flash_attention(q, k, v, interpret=interp,
                                   **kw).astype(jnp.float32).sum()
        rows.append(_gate(f"flash_bwd_{name}",
                          jax.value_and_grad(f, argnums=(0, 1, 2)),
                          q, kk, vv))

    flash_fwd("causal", causal=True)
    flash_fwd("full", causal=False)
    flash_fwd("alibi", causal=True, alibi_slopes=slopes)
    flash_fwd("window", causal=True, window=256)
    flash_fwd("alibi_window", causal=True, alibi_slopes=slopes, window=256)
    rows.append(_gate("flash_fwd_gqa",
                      lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                      interpret=interp),
                      q, kg, vg))
    flash_bwd("causal", causal=True)
    flash_bwd("alibi", causal=True, alibi_slopes=slopes)
    flash_bwd("window", causal=True, window=256)
    flash_bwd("gqa", kk=kg, vv=vg, causal=True)
    # the shape the mellum training cell runs: 32 heads of 128 on 4 kv
    # heads (group 8) at 8,192 positions under its window of 1,024 (a
    # traced scalar there).  Compile only, so the gate can afford it; the
    # interpreter's smoke keeps the gate's own sequence
    S8 = S if interp else 8192
    q8 = jax.ShapeDtypeStruct((1, S8, 32, 128), jnp.bfloat16)
    k8 = jax.ShapeDtypeStruct((1, S8, 4, 128), jnp.bfloat16)
    rows.append(_gate(
        "flash_bwd_gqa8_window_s8192",
        jax.value_and_grad(
            lambda q, k, v, w: flash_attention(
                q, k, v, causal=True, window=w,
                interpret=interp).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)),
        q8, k8, k8, jax.ShapeDtypeStruct((), jnp.int32)))

    # decode: contiguous + paged caches (serving path)
    qd = jax.random.normal(rng, (B, 1, H, D), jnp.bfloat16)
    kc = jax.random.normal(rng, (B, 2, S, D), jnp.bfloat16)
    lengths = jnp.full((B,), S // 2, jnp.int32)
    rows.append(_gate("decode_contiguous",
                      lambda q, k, v, ln: decode_attention_pallas(
                          q, k, v, ln, interpret=interp),
                      qd, kc, kc, lengths))
    page, npages = 128, S // 128
    kp = jax.random.normal(rng, (npages * B, 2, page, D), jnp.bfloat16)
    tables = jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
    rows.append(_gate("decode_paged",
                      lambda q, kp, vp, t, ln: ragged_paged_attention_rect(
                          q, kp, vp, t, ln, interpret=interp),
                      qd, kp, kp, tables, lengths))

    # fused ragged paged attention (one kernel, mixed prefill+decode):
    # gate pure-decode, pure-prefill, and mixed ragged shapes over the
    # same page pool — q_lens is host metadata, so it closes over the fn

    def ragged(name, q_lens, ctx_lens):
        qr = jax.random.normal(rng, (sum(q_lens), H, D), jnp.bfloat16)
        ctx = jnp.asarray(ctx_lens, jnp.int32)
        rows.append(_gate(
            f"ragged_{name}",
            lambda q, kp, vp, t, c: ragged_paged_attention(
                q, kp, vp, t, c, q_lens, interpret=interp),
            qr, kp, kp, tables, ctx))

    ragged("decode", [1] * B, [S // 2] * B)
    ragged("prefill", [256] * B, [256] * B)
    ragged("mixed", [256, 1], [256, S // 2])

    # dense latent prefill (one kernel over the pool: the cached entries
    # and the chunk itself), at Kimi-K2's widths: a chunk from an empty
    # pool (the causal blocks alone), a chunk over cached context, and
    # several sequences of unlike lengths with padded rows
    from deepspeed_tpu.ops.latent_attention import init_latent_pools
    from deepspeed_tpu.ops.pallas.latent_attention import \
        latent_prefill_attention
    Hl, dn, dr, dv, R = 8, 128, 64, 128, 512
    lat_pool = init_latent_pools(2, 3 * npages + 1, page, R + dr,
                                 0).latent_pages
    w_kvb = jax.random.normal(rng, (R, Hl * (dn + dv)), jnp.bfloat16)

    def latent(name, Bl, T, cached, real):
        qn = jax.random.normal(rng, (Bl, T, Hl, dn), jnp.bfloat16)
        qr = jax.random.normal(rng, (Bl, T, Hl, dr), jnp.bfloat16)
        tbl = 1 + jnp.arange(Bl * npages, dtype=jnp.int32).reshape(
            Bl, npages)
        rows.append(_gate(
            f"latent_prefill_{name}",
            lambda qn, qr, pool, t, ln, w, rl: latent_prefill_attention(
                qn, qr, pool, 1, t, ln, w, 0.07, real_lengths=rl,
                interpret=interp),
            qn, qr, lat_pool, tbl, jnp.asarray(cached, jnp.int32), w_kvb,
            jnp.asarray(real, jnp.int32)))

    latent("causal", 1, S // 2, [0], [S // 2])
    latent("context", 1, S // 2, [S // 2], [S // 2])
    latent("batch", 3, S // 4, [S // 2, 0, 3 * page + 5],
           [S // 4, S // 8 + 3, 0])

    # the decode step's one-row state-space update, in place in the stacked
    # state pool: granite-4.0-h-micro's widths at the cell's 64 slots (two
    # layers of its 36, the layer traced), the same with slots the
    # dispatch does not serve, and the toy engine's widths in float32
    from deepspeed_tpu.ops.ssm import state_decode_update

    def state(name, slots, Hs, P, Ns, G, dtype, live=None):
        live = jnp.ones((slots,), bool) if live is None else live
        rows.append(_gate(
            f"ssm_update_{name}",
            lambda pool, lay, x, dt, a, b, c, d: state_decode_update(
                pool, lay, x, dt, a, b, c, d, live, impl="pallas",
                interpret=interp),
            jnp.zeros((2, slots, Hs, P, Ns), jnp.float32),
            jnp.asarray(1, jnp.int32), jnp.zeros((slots, Hs, P), dtype),
            jnp.ones((slots, Hs), jnp.float32), -jnp.ones((Hs,)),
            jnp.zeros((slots, G, Ns), dtype),
            jnp.zeros((slots, G, Ns), dtype), jnp.ones((Hs,))))

    state("cell", 64, 64, 64, 128, 1, jnp.bfloat16)
    state("dead_rows", 64, 64, 64, 128, 1, jnp.bfloat16,
          live=jnp.arange(64) % 3 > 0)
    state("toy", 3, 16, 32, 32, 1, jnp.float32)

    # sparse attention (fixed local+global layout)
    block, nb = 128, S // 128
    layout = np.zeros((H, nb, nb), np.int64)
    for i in range(nb):
        layout[:, i, max(0, i - 2):i + 1] = 1
        layout[:, i, 0] = 1
    rows.append(_gate("sparse_fixed",
                      lambda q, k, v: sparse_attention_pallas(
                          q, k, v, layout, block, causal=True,
                          interpret=interp),
                      q, k, v))

    # fused Adam (flat update kernel)
    from deepspeed_tpu.ops.adam import AdamState
    n = 1 << 20
    p = jnp.zeros((n,), jnp.float32)
    st = AdamState(m=jnp.zeros((n,), jnp.float32),
                   v=jnp.zeros((n,), jnp.float32),
                   step=jnp.asarray(0, jnp.int32))
    rows.append(_gate("fused_adam",
                      lambda p, g, st: fused_adam_pallas(
                          p, g, st, interpret=interp),
                      p, p, st))

    summary = {"all_ok": all(r["ok"] for r in rows),
               "n_variants": len(rows),
               "failed": [r["variant"] for r in rows if not r["ok"]],
               "backend": jax.devices()[0].platform,
               "device_kind": getattr(jax.devices()[0], "device_kind", "")}
    print(json.dumps(summary))
    if args.json_out:
        out_dir = os.path.dirname(os.path.abspath(args.json_out))
        os.makedirs(out_dir, exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0 if summary["all_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
