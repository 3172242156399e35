"""Ring attention over the ``sp`` axis — exact blockwise attention for
sequences too long for any single device.

The reference has no context parallelism (SURVEY §2.4); its long-sequence
story was block-*sparse* attention.  This implements the exact alternative
(Ring Attention with blockwise online softmax): each device keeps its local
Q block resident and K/V blocks rotate around the ``sp`` ring via
``ppermute``; partial results merge with the flash-attention log-sum-exp
recurrence.  XLA overlaps each hop's transfer with the current block's
compute.

Memory: the forward materialises only [S/sp, S/sp] scores per step, and the
backward is a **custom VJP** that re-rotates K/V and recomputes each block
from the saved log-sum-exp — per-device residuals stay O(S/sp), never the
full sequence.  K/V stay at their GQA head count through the ring (the query
group dim is folded into the block einsums), so ppermute traffic is Hkv-sized.

Causal FLOPs: fully-masked future blocks (kv past the device's own
sequence position) are skipped with a per-device ``lax.cond`` — the ring
still rotates every hop (collectives stay outside the branch) but only
n(n+1)/2 of the n^2 block products are computed.  With the default
contiguous layout this is a FLOPs/energy saving, NOT wall-clock: the
lockstep ppermute after each hop synchronizes the ring, and the last
device computes a full block on every hop while earlier devices idle.
``layout="zigzag"`` converts it into step time: tokens are permuted so
device d owns chunks (d, 2n-1-d) — every device holds early AND late
positions, each (device, hop) computes ~2 of its 4 chunk sub-blocks, and
the causal triangle is balanced across the ring (~2x at large sp).
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import BATCH_AXES, SP_AXIS
from deepspeed_tpu.runtime.zero.stage_plan import active_mesh

_NEG = -1e30


def _rotate(x, axis_name, n):
    return jax.lax.ppermute(x, axis_name, [(j, (j + 1) % n) for j in range(n)])


def _block_scores(q5, k, scale, mask):
    """q5: [B, Sq, Hkv, G, D]; k: [B, Sk, Hkv, D] → scores [B, Hkv, G, Sq, Sk]
    in fp32 (GQA group folded into the einsum — K stays at Hkv heads)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, _NEG)
    return s


def _causal_mask(my_idx, kv_idx, S):
    pos = jnp.arange(S)
    qpos = my_idx * S + pos[:, None]
    kpos = kv_idx * S + pos[None, :]
    return qpos >= kpos


def _ring_fwd_local(q, k, v, axis_name, causal, scale):
    """Returns (out [B,S,H,D], lse [B,Hkv,G,S])."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    q5 = q.reshape(B, S, Hkv, G, D)
    n = axis_size(axis_name)
    # only the causal mask/skip needs this device's ring position; the
    # non-causal path must not touch axis_index (it lowers to PartitionId,
    # which the SPMD partitioner rejects even when the value is dead)
    my_idx = jax.lax.axis_index(axis_name) if causal else 0

    o0 = jnp.zeros((B, S, Hkv, G, D), jnp.float32)
    m0 = jnp.full((B, Hkv, G, S), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S), jnp.float32)

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        kv_idx = (my_idx - i) % n

        def compute(acc):
            o, m, l = acc
            mask = _causal_mask(my_idx, kv_idx, S) if causal else None
            s = _block_scores(q5, k_cur, scale, mask)  # [B,Hkv,G,Sq,Sk]
            bm = jnp.max(s, axis=-1)
            new_m = jnp.maximum(m, bm)
            p = jnp.exp(s - new_m[..., None])
            p = jnp.where(new_m[..., None] <= _NEG / 2, 0.0, p)
            corr = jnp.exp(m - new_m)
            corr = jnp.where(m <= _NEG / 2, 0.0, corr)
            l2 = l * corr + jnp.sum(p, axis=-1)
            bo = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cur.dtype),
                            v_cur).astype(jnp.float32)
            corr_o = jnp.moveaxis(corr, 3, 1)[..., None]  # [B,Sq,Hkv,G,1]
            return o * corr_o + bo, new_m, l2

        if causal:
            # future blocks (kv_idx > my_idx) are fully masked: their
            # contribution is exactly zero, so SKIP the compute entirely —
            # per-device lax.cond inside shard_map; the ring ppermutes stay
            # outside so every device still participates in every hop.
            # n(n+1)/2 of n^2 blocks computed — a FLOPs/energy saving;
            # wall-clock needs zig-zag placement (module docstring).
            o, m, l = jax.lax.cond(kv_idx <= my_idx, compute,
                                   lambda acc: acc, (o, m, l))
        else:
            o, m, l = compute((o, m, l))
        return o, m, l, _rotate(k_cur, axis_name, n), \
            _rotate(v_cur, axis_name, n)

    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    l_safe = jnp.maximum(l, 1e-30)
    out = o / jnp.moveaxis(l_safe, 3, 1)[..., None]
    lse = m + jnp.log(l_safe)
    return out.reshape(B, S, H, D).astype(q.dtype), lse


def _ring_bwd_local(q, k, v, out, lse, g, axis_name, causal, scale):
    """Recompute-with-rotation backward: dk/dv accumulators travel with the
    rotating K/V blocks and arrive home after n hops."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    q5 = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    g5 = g.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    o5 = out.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    delta = jnp.sum(g5 * o5, axis=-1)                  # [B,S,Hkv,G]
    delta = jnp.moveaxis(delta, 1, 3)                  # [B,Hkv,G,S]
    n = axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name) if causal else 0  # see fwd note

    dq0 = jnp.zeros_like(q5)
    dk0 = jnp.zeros((B, S, Hkv, D), jnp.float32)
    dv0 = jnp.zeros((B, S, Hkv, D), jnp.float32)

    def body(i, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        kv_idx = (my_idx - i) % n

        def compute(acc):
            dq, dk_c, dv_c = acc
            mask = _causal_mask(my_idx, kv_idx, S) if causal else None
            s = _block_scores(q5, k_cur, scale, mask)
            p = jnp.exp(s - lse[..., None])            # [B,Hkv,G,Sq,Sk]
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", g5,
                            v_cur.astype(jnp.float32))
            ds = p * (dp - delta[..., None]) * scale
            dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                 k_cur.astype(jnp.float32))
            dk_c = dk_c + jnp.einsum("bhgqk,bqhgd->bkhd", ds, q5)
            dv_c = dv_c + jnp.einsum("bhgqk,bqhgd->bkhd", p, g5)
            return dq, dk_c, dv_c

        if causal:
            # mirror of the forward skip: fully-masked future blocks
            # contribute exact zeros to dq/dk/dv
            dq, dk_cur, dv_cur = jax.lax.cond(
                kv_idx <= my_idx, compute, lambda acc: acc,
                (dq, dk_cur, dv_cur))
        else:
            dq, dk_cur, dv_cur = compute((dq, dk_cur, dv_cur))
        return (dq, _rotate(k_cur, axis_name, n), _rotate(v_cur, axis_name, n),
                _rotate(dk_cur, axis_name, n), _rotate(dv_cur, axis_name, n))

    dq, _, _, dk, dv = jax.lax.fori_loop(0, n, body, (dq0, k, v, dk0, dv0))
    # after n rotations the accumulators are back at the owner of their block
    return (dq.reshape(B, S, H, D).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention_local(q, k, v, axis_name=SP_AXIS, causal=True,
                         softmax_scale=None):
    """Per-device body (inside shard_map): q/k/v [B, S_loc, H|Hkv, D] are this
    device's sequence block; returns the local attention output."""
    scale = softmax_scale if softmax_scale is not None else \
        1.0 / math.sqrt(q.shape[-1])
    out, _ = _ring_fwd_local(q, k, v, axis_name, causal, scale)
    return out


def _ring_local_fwd(q, k, v, axis_name, causal, softmax_scale):
    scale = softmax_scale if softmax_scale is not None else \
        1.0 / math.sqrt(q.shape[-1])
    out, lse = _ring_fwd_local(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_local_bwd(axis_name, causal, softmax_scale, res, g):
    q, k, v, out, lse = res
    scale = softmax_scale if softmax_scale is not None else \
        1.0 / math.sqrt(q.shape[-1])
    return _ring_bwd_local(q, k, v, out, lse, g, axis_name, causal, scale)


ring_attention_local.defvjp(_ring_local_fwd, _ring_local_bwd)


# ----------------------------------------------------------------------
# Zig-zag layout: device d owns chunks (d, 2n-1-d) of 2n global chunks.
# Every device holds both EARLY and LATE positions, so the causal triangle
# is ~evenly split: each (device, hop) pair computes ~2 of its 4 chunk
# sub-blocks — the wall-clock realisation of the triangle saving the
# contiguous layout can only bank as FLOPs (module docstring).
# ----------------------------------------------------------------------

def zigzag_perm(S: int, n: int):
    """Global token permutation: new order = concat_d [chunk_d,
    chunk_{2n-1-d}] over devices d (2n chunks of S/(2n))."""
    assert S % (2 * n) == 0, f"S={S} must divide into 2*sp={2 * n} chunks"
    c = S // (2 * n)
    import numpy as _onp
    order = []
    for d in range(n):
        order.extend(range(d * c, (d + 1) * c))
        order.extend(range((2 * n - 1 - d) * c, (2 * n - d) * c))
    perm = _onp.asarray(order)
    inv = _onp.empty_like(perm)
    inv[perm] = _onp.arange(S)
    return perm, inv


def _zz_fwd_local(q, k, v, axis_name, scale):
    """Zig-zag causal forward.  Local block = [early chunk | late chunk]
    (each length c); 2x2 chunk sub-blocks per hop, fully-in-future ones
    skipped per device.  Returns (out, lse) like the contiguous kernel."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    c = S // 2
    n = axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    q5 = q.reshape(B, S, Hkv, G, D)
    ar = jnp.arange(c)

    def chunk_id(owner, half):
        return jnp.where(half == 0, owner, 2 * n - 1 - owner)

    # per-half accumulators [B, c, Hkv, G, D] / [B, Hkv, G, c]
    o0 = jnp.zeros((B, S, Hkv, G, D), jnp.float32)
    m0 = jnp.full((B, Hkv, G, S), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S), jnp.float32)

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        j = (my_idx - i) % n
        for qh in (0, 1):
            qc_id = chunk_id(my_idx, qh)
            q_half = q5[:, qh * c:(qh + 1) * c]
            o_h = o[:, qh * c:(qh + 1) * c]
            m_h = m[..., qh * c:(qh + 1) * c]
            l_h = l[..., qh * c:(qh + 1) * c]
            for kh in (0, 1):
                kc_id = chunk_id(j, kh)
                k_half = k_cur[:, kh * c:(kh + 1) * c]
                v_half = v_cur[:, kh * c:(kh + 1) * c]

                def compute(acc, q_half=q_half, k_half=k_half,
                            v_half=v_half, qc_id=qc_id, kc_id=kc_id):
                    o_h, m_h, l_h = acc
                    qpos = qc_id * c + ar[:, None]
                    kpos = kc_id * c + ar[None, :]
                    s = _block_scores(q_half, k_half, scale, qpos >= kpos)
                    bm = jnp.max(s, axis=-1)
                    new_m = jnp.maximum(m_h, bm)
                    p = jnp.exp(s - new_m[..., None])
                    p = jnp.where(new_m[..., None] <= _NEG / 2, 0.0, p)
                    corr = jnp.exp(m_h - new_m)
                    corr = jnp.where(m_h <= _NEG / 2, 0.0, corr)
                    l2 = l_h * corr + jnp.sum(p, axis=-1)
                    bo = jnp.einsum("bhgqk,bkhd->bqhgd",
                                    p.astype(v_half.dtype),
                                    v_half).astype(jnp.float32)
                    corr_o = jnp.moveaxis(corr, 3, 1)[..., None]
                    return o_h * corr_o + bo, new_m, l2

                o_h, m_h, l_h = jax.lax.cond(
                    qc_id >= kc_id, compute, lambda a: a, (o_h, m_h, l_h))
            o = jax.lax.dynamic_update_slice_in_dim(o, o_h, qh * c, 1)
            m = jax.lax.dynamic_update_slice_in_dim(m, m_h, qh * c, 3)
            l = jax.lax.dynamic_update_slice_in_dim(l, l_h, qh * c, 3)
        return o, m, l, _rotate(k_cur, axis_name, n), \
            _rotate(v_cur, axis_name, n)

    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    l_safe = jnp.maximum(l, 1e-30)
    out = o / jnp.moveaxis(l_safe, 3, 1)[..., None]
    lse = m + jnp.log(l_safe)
    return out.reshape(B, S, H, D).astype(q.dtype), lse


def _zz_bwd_local(q, k, v, out, lse, g, axis_name, scale):
    """Zig-zag backward: same sub-block skip; dk/dv accumulators travel
    with the rotating K/V and arrive home after n hops."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    c = S // 2
    n = axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    ar = jnp.arange(c)
    q5 = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    g5 = g.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    o5 = out.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    delta = jnp.moveaxis(jnp.sum(g5 * o5, axis=-1), 1, 3)   # [B,Hkv,G,S]

    def chunk_id(owner, half):
        return jnp.where(half == 0, owner, 2 * n - 1 - owner)

    dq0 = jnp.zeros_like(q5)
    dk0 = jnp.zeros((B, S, Hkv, D), jnp.float32)
    dv0 = jnp.zeros((B, S, Hkv, D), jnp.float32)

    def body(i, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        j = (my_idx - i) % n
        for qh in (0, 1):
            qc_id = chunk_id(my_idx, qh)
            q_half = q5[:, qh * c:(qh + 1) * c]
            g_half = g5[:, qh * c:(qh + 1) * c]
            lse_h = lse[..., qh * c:(qh + 1) * c]
            delta_h = delta[..., qh * c:(qh + 1) * c]
            dq_h = dq[:, qh * c:(qh + 1) * c]
            for kh in (0, 1):
                kc_id = chunk_id(j, kh)
                k_half = k_cur[:, kh * c:(kh + 1) * c]
                v_half = v_cur[:, kh * c:(kh + 1) * c]
                dk_h = jax.lax.dynamic_slice_in_dim(dk_cur, kh * c, c, 1)
                dv_h = jax.lax.dynamic_slice_in_dim(dv_cur, kh * c, c, 1)

                def compute(acc, q_half=q_half, g_half=g_half,
                            k_half=k_half, v_half=v_half, lse_h=lse_h,
                            delta_h=delta_h, qc_id=qc_id, kc_id=kc_id):
                    dq_h, dk_h, dv_h = acc
                    qpos = qc_id * c + ar[:, None]
                    kpos = kc_id * c + ar[None, :]
                    s = _block_scores(q_half, k_half, scale, qpos >= kpos)
                    p = jnp.exp(s - lse_h[..., None])
                    dp = jnp.einsum("bqhgd,bkhd->bhgqk", g_half,
                                    v_half.astype(jnp.float32))
                    ds = p * (dp - delta_h[..., None]) * scale
                    dq_h = dq_h + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                             k_half.astype(jnp.float32))
                    dk_h = dk_h + jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                                             q_half)
                    dv_h = dv_h + jnp.einsum("bhgqk,bqhgd->bkhd", p,
                                             g_half)
                    return dq_h, dk_h, dv_h

                dq_h, dk_h, dv_h = jax.lax.cond(
                    qc_id >= kc_id, compute, lambda a: a,
                    (dq_h, dk_h, dv_h))
                dk_cur = jax.lax.dynamic_update_slice_in_dim(
                    dk_cur, dk_h, kh * c, 1)
                dv_cur = jax.lax.dynamic_update_slice_in_dim(
                    dv_cur, dv_h, kh * c, 1)
            dq = jax.lax.dynamic_update_slice_in_dim(dq, dq_h, qh * c, 1)
        return (dq, _rotate(k_cur, axis_name, n),
                _rotate(v_cur, axis_name, n),
                _rotate(dk_cur, axis_name, n),
                _rotate(dv_cur, axis_name, n))

    dq, _, _, dk, dv = jax.lax.fori_loop(0, n, body, (dq0, k, v, dk0, dv0))
    return (dq.reshape(B, S, H, D).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def zigzag_ring_attention_local(q, k, v, axis_name=SP_AXIS,
                                softmax_scale=None):
    scale = softmax_scale if softmax_scale is not None else \
        1.0 / math.sqrt(q.shape[-1])
    out, _ = _zz_fwd_local(q, k, v, axis_name, scale)
    return out


def _zz_local_fwd(q, k, v, axis_name, softmax_scale):
    scale = softmax_scale if softmax_scale is not None else \
        1.0 / math.sqrt(q.shape[-1])
    out, lse = _zz_fwd_local(q, k, v, axis_name, scale)
    return out, (q, k, v, out, lse)


def _zz_local_bwd(axis_name, softmax_scale, res, g):
    q, k, v, out, lse = res
    scale = softmax_scale if softmax_scale is not None else \
        1.0 / math.sqrt(q.shape[-1])
    return _zz_bwd_local(q, k, v, out, lse, g, axis_name, scale)


zigzag_ring_attention_local.defvjp(_zz_local_fwd, _zz_local_bwd)


def ring_attention(q, k, v, causal=True, softmax_scale=None, mesh=None,
                   layout="contiguous"):
    """GSPMD entry: q/k/v global [B, S, H|Hkv, D], sequence-sharded over
    ``sp``.  ``layout="zigzag"`` (causal only) permutes tokens so every
    device owns early AND late positions — balanced causal work, ~2x
    step-time at large sp (the permutation gathers lower to one
    all-to-all per tensor)."""
    mesh = mesh or active_mesh()
    if mesh is None or mesh.shape.get(SP_AXIS, 1) == 1:
        from deepspeed_tpu.ops.attention import reference_attention
        return reference_attention(q, k, v, causal=causal,
                                   softmax_scale=softmax_scale)
    spec = P(tuple(BATCH_AXES), SP_AXIS, None, None)
    if layout == "zigzag":
        assert causal, "zigzag layout only makes sense for causal attention"
        n = mesh.shape[SP_AXIS]
        perm, inv = zigzag_perm(q.shape[1], n)
        qz, kz, vz = (x[:, perm] for x in (q, k, v))
        body = jax.shard_map(
            lambda q, k, v: zigzag_ring_attention_local(
                q, k, v, SP_AXIS, softmax_scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return body(qz, kz, vz)[:, inv]
    body = jax.shard_map(
        # positional call: custom_vjp nondiff_argnums are positional
        lambda q, k, v: ring_attention_local(q, k, v, SP_AXIS, causal,
                                             softmax_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return body(q, k, v)
