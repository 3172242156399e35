"""Pallas fused AdamW over flat partition buffers.

Parity: reference ``csrc/adam/multi_tensor_adam.cu`` (``multi_tensor_adam``)
— the CUDA multi-tensor AdamW used by ZeRO.  The reference fuses the whole
update into one kernel launch over chunked tensor lists; here the ZeRO
partition layout is already a flat buffer, so one Pallas kernel tiles it
through VMEM and the update never round-trips HBM between its ~10
elementwise ops.  Outputs alias the inputs (in-place, like the CUDA op).

``ops/adam.py:reference_impl`` is the jnp oracle; CPU CI runs this kernel
with ``interpret=True``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 512        # 512x128 fp32 x 7 live buffers ≈ 1.8 MB VMEM


def _adam_kernel(scalars_ref, p_ref, g_ref, m_ref, v_ref,
                 out_p_ref, out_m_ref, out_v_ref, *,
                 beta1, beta2, eps, weight_decay, adamw_mode):
    c1 = scalars_ref[0]      # 1 - beta1**step   (1.0 if no bias correction)
    c2 = scalars_ref[1]      # 1 - beta2**step
    lr = scalars_ref[2]
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    if not adamw_mode and weight_decay:       # L2-regularised Adam (mode 1)
        g = g + weight_decay * p
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * (g * g)
    update = (m / c1) / (jnp.sqrt(v / c2) + eps)
    if adamw_mode and weight_decay:           # decoupled decay (mode 0)
        update = update + weight_decay * p
    out_p_ref[...] = (p - lr * update).astype(out_p_ref.dtype)
    out_m_ref[...] = m
    out_v_ref[...] = v


def fused_adam_pallas(params, grads, state, lr=1e-3, beta1=0.9, beta2=0.999,
                      eps=1e-8, weight_decay=0.0, adamw_mode=True,
                      bias_correction=True, interpret=False):
    """One fused AdamW step on a flat buffer.  Same contract as
    ``ops/adam.py:reference_impl``: returns (new_params, new_state)."""
    from deepspeed_tpu.ops.adam import AdamState

    n = params.size
    step = state.step + 1
    sf = step.astype(jnp.float32)
    c1 = 1.0 - beta1 ** sf if bias_correction else jnp.float32(1.0)
    c2 = 1.0 - beta2 ** sf if bias_correction else jnp.float32(1.0)
    scalars = jnp.stack([jnp.asarray(c1, jnp.float32),
                         jnp.asarray(c2, jnp.float32),
                         jnp.asarray(lr, jnp.float32)])

    # pad + tile the flat buffer to [rows, 128]
    tile = _BLOCK_ROWS * _LANES
    n_pad = -n % tile
    def shape2d(x):
        x = x.reshape(-1)
        if n_pad:
            x = jnp.pad(x, (0, n_pad))
        return x.reshape(-1, _LANES)

    p2 = shape2d(params)
    g2 = shape2d(grads)
    m2 = shape2d(state.m)
    v2 = shape2d(state.v)
    rows = p2.shape[0]
    grid = (rows // _BLOCK_ROWS,)

    kernel = functools.partial(
        _adam_kernel, beta1=beta1, beta2=beta2, eps=eps,
        weight_decay=weight_decay, adamw_mode=adamw_mode)
    block = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i, scalars: (i, 0))
    new_p, new_m, new_v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[block] * 4,
            out_specs=[block] * 3,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, p2.dtype),
            jax.ShapeDtypeStruct(m2.shape, jnp.float32),
            jax.ShapeDtypeStruct(v2.shape, jnp.float32),
        ],
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
    )(scalars, p2, g2, m2, v2)

    unpad = lambda x: x.reshape(-1)[:n]
    return unpad(new_p).reshape(params.shape), AdamState(
        m=unpad(new_m).reshape(params.shape),
        v=unpad(new_v).reshape(params.shape), step=step)
