"""Attention ops: reference implementation + Pallas flash attention.

Parity role: reference ``csrc/transformer`` fused training attention
(``ds_transformer_cuda.cpp``) and ``deepspeed/ops/sparse_attention`` — the
compute-bound inner loop of the transformer.  TPU design: a Pallas
flash-attention kernel (tiled online-softmax over VMEM blocks feeding the MXU)
with a jnp reference implementation that is the CPU path and the test
oracle.

``attention()`` is the public entry: picks Pallas on TPU, jnp on the CPU.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import BATCH_AXES, TP_AXIS


def reference_attention(q, k, v, causal=True, bias=None, segment_ids=None,
                        softmax_scale: Optional[float] = None,
                        logit_softcap: Optional[float] = None):
    """Plain softmax attention.

    q: [B, S, H, D]; k/v: [B, S, Hkv, D] (Hkv divides H → GQA).
    Softmax in fp32 regardless of input dtype (reference kernels do the same).
    """
    orig_dtype = q.dtype
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if logit_softcap:
        # Gemma-2 style: bounded raw scores, applied BEFORE mask/bias
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    Sk = k.shape[1]
    if bias is not None:
        logits = logits + bias
    if causal:
        qi = jnp.arange(Sq)[:, None] + (Sk - Sq)
        ki = jnp.arange(Sk)[None, :]
        mask = qi >= ki
        logits = jnp.where(mask[None, None], logits, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(orig_dtype)


# the Pallas kernel lives in ops/pallas/flash_attention.py and is
# substituted when running on TPU.
reference_impl = reference_attention


def alibi_window_bias(Sq, Sk, slopes=None, window=None):
    """Additive attention bias for ALiBi slopes and/or a sliding window —
    THE shared construction (model `_attn_bias`, flash fallback): ALiBi is
    ``slope * kpos`` (row-constant part cancels in softmax) and the window
    allows ``qpos - kpos < w`` with ``w <= 0`` meaning unlimited.  Query
    rows are aligned to the END of the key range (``Sq != Sk`` decode)."""
    import jax.numpy as jnp
    bias = None
    if slopes is not None:
        bias = (jnp.asarray(slopes, jnp.float32)[None, :, None, None]
                * jnp.arange(Sk, dtype=jnp.float32)[None, None, None, :])
    if window is not None:
        qpos = jnp.arange(Sq, dtype=jnp.int32)[:, None] + (Sk - Sq)
        kpos = jnp.arange(Sk, dtype=jnp.int32)[None, :]
        w = jnp.asarray(window).astype(jnp.int32)
        wbias = jnp.where((qpos - kpos < w) | (w <= 0), 0.0,
                          -1e30).astype(jnp.float32)[None, None]
        bias = wbias if bias is None else bias + wbias
    return bias


@functools.partial(jax.jit, static_argnames=("causal", "softmax_scale",
                                             "impl", "block_q", "block_k",
                                             "interpret", "logit_softcap"))
def attention(q, k, v, causal=True, softmax_scale=None, impl="auto",
              block_q=None, block_k=None, alibi_slopes=None, window=None,
              interpret=False, logit_softcap=None):
    """Dispatching attention entry point — the ONE place the
    pallas-vs-reference policy lives.

    Where the flash kernel cannot serve the call (a shape that does not
    tile or does not divide over the mesh, ``logit_softcap``),
    ``impl="pallas"`` raises and ``impl="auto"`` — which takes the kernel
    everywhere but on the CPU — takes the reference and logs that once per
    reason through :func:`_warn_fallback`, never silently.  A kernel that
    fails to trace raises under every ``impl``.

    ``block_q``/``block_k`` override the Pallas flash tiles (None = the
    kernel picks them from the call's shapes, ``pick_flash_tiles``) and
    MUST be static (they pick the Pallas grid).
    ``alibi_slopes`` ([H]) and ``window`` (traced scalar, 0/None =
    unlimited) ride the flash kernel's in-kernel bias on the Pallas path
    and a materialized :func:`alibi_window_bias` on the reference path.
    ``interpret`` (static) runs the kernel in the Pallas interpreter (CPU
    CI)."""
    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          flash_tiles)
    mesh = _mesh_to_shard_over()
    batch_ways = head_ways = 1
    if mesh is not None:
        batch_ways = math.prod(mesh.shape.get(a, 1) for a in BATCH_AXES)
        head_ways = mesh.shape.get(TP_AXIS, 1)
    why_not = None
    if logit_softcap:
        # tanh capping lives inside the softmax loop; the flash kernel
        # does not implement it yet — XLA fuses the jnp path fine
        why_not = "logit_softcap is not implemented in the kernel"
    elif not flash_tiles(q.shape[1], q.shape[2], k.shape[2],
                         block_q, block_k):
        why_not = (f"q{q.shape} k{k.shape} does not tile "
                   f"block_q={block_q or 'picked'} "
                   f"block_k={block_k or 'picked'}")
    elif q.shape[0] % batch_ways or k.shape[2] % head_ways:
        why_not = (f"q{q.shape} k{k.shape} does not divide over the mesh "
                   f"({batch_ways} batch x {head_ways} head shards)")
    if impl == "pallas" and why_not:
        raise ValueError(f"impl='pallas': {why_not}")
    use_pallas = impl == "pallas"
    if impl == "auto" and jax.default_backend() != "cpu":
        use_pallas = why_not is None
        if why_not:
            _warn_fallback(why_not)
    if use_pallas:
        flash = functools.partial(
            flash_attention, causal=causal, softmax_scale=softmax_scale,
            block_q=block_q, block_k=block_k, interpret=interpret)
        if mesh is None:
            return flash(q, k, v, alibi_slopes=alibi_slopes, window=window)
        return _flash_per_shard(flash, mesh, q, k, v, alibi_slopes, window)
    bias = None
    if alibi_slopes is not None or window is not None:
        bias = alibi_window_bias(q.shape[1], k.shape[1],
                                 slopes=alibi_slopes, window=window)
    return reference_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale, bias=bias,
                               logit_softcap=logit_softcap)


def _mesh_to_shard_over():
    """The ambient ``with mesh:`` mesh when XLA would have to partition
    the call over it; None on one device and inside a ``shard_map`` body
    (ring / ulysses / pipeline), where the arrays are already per shard."""
    from deepspeed_tpu.runtime.zero.stage_plan import active_mesh
    mesh = active_mesh()
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


def _flash_per_shard(flash, mesh, q, k, v, alibi_slopes, window):
    """XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so on a mesh the kernel runs per shard
    under ``shard_map``: batch over the data axes, heads over tp — the
    layout the model's activations already have.  The sequence dim stays
    whole (sequence parallelism is ring/ulysses' job)."""
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    head_axis = TP_AXIS if TP_AXIS in mesh.axis_names else None
    qkv = P(batch_axes or None, None, head_axis, None)
    if alibi_slopes is not None:
        alibi_slopes = jnp.asarray(alibi_slopes, jnp.float32)
    if window is not None:
        window = jnp.asarray(window)
    # an absent (None) slopes/window is an empty pytree under its spec
    return jax.shard_map(
        lambda q, k, v, slopes, win: flash(q, k, v, alibi_slopes=slopes,
                                           window=win),
        mesh=mesh, in_specs=(qkv, qkv, qkv, P(head_axis), P()),
        out_specs=qkv, check_vma=False)(q, k, v, alibi_slopes, window)


@functools.lru_cache(maxsize=8)
def _warn_fallback(reason: str):
    """A silent fallback once hid a tracer bug that disabled the flash
    kernel entirely (-30% train throughput); never choose quietly."""
    from deepspeed_tpu.utils.logging import logger
    logger.warning(f"impl='auto' chose jnp reference attention over the "
                   f"flash kernel: {reason}")
