"""Fused Adam over flat partition buffers.

Parity: reference ``csrc/adam/fused_adam_frontend.cpp`` + ``multi_tensor_adam.cu``
(``multi_tensor_adam``) — the CUDA multi-tensor AdamW used by ZeRO.

TPU design: the optimizer math is expressed once over a flat 1-D buffer (the
ZeRO partition layout); under jit XLA fuses it into a single VPU loop, which
is what the CUDA multi-tensor apply hand-builds.  A Pallas version
(``ops/pallas/fused_adam.py``) exists for the HBM-bound regime; this jnp
implementation is the reference/oracle and the CPU fallback.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    m: jnp.ndarray
    v: jnp.ndarray
    step: jnp.ndarray


def init_state(params_flat: jnp.ndarray) -> AdamState:
    return AdamState(
        m=jnp.zeros_like(params_flat, dtype=jnp.float32),
        v=jnp.zeros_like(params_flat, dtype=jnp.float32),
        step=jnp.zeros((), jnp.int32))


def reference_impl(params, grads, state: AdamState, lr=1e-3, beta1=0.9,
                   beta2=0.999, eps=1e-8, weight_decay=0.0, adamw_mode=True,
                   bias_correction=True):
    """One fused AdamW update on flat fp32 buffers.  Returns (params, state).

    Mirrors the update in ``multi_tensor_adam.cu`` (ADAM_MODE 0/1).
    """
    g = grads.astype(jnp.float32)
    p = params.astype(jnp.float32)
    step = state.step + 1
    if not adamw_mode and weight_decay:   # L2-regularised Adam (mode 1)
        g = g + weight_decay * p
    m = beta1 * state.m + (1.0 - beta1) * g
    v = beta2 * state.v + (1.0 - beta2) * jnp.square(g)
    if bias_correction:
        sf = jnp.float32(step)
        m_hat = m / (1.0 - beta1 ** sf)
        v_hat = v / (1.0 - beta2 ** sf)
    else:
        m_hat, v_hat = m, v
    update = m_hat / (jnp.sqrt(v_hat) + eps)
    if adamw_mode and weight_decay:       # decoupled decay (mode 0)
        update = update + weight_decay * p
    new_p = p - lr * update
    return new_p.astype(params.dtype), AdamState(m=m, v=v, step=step)


def fused_adam(params, grads, state, **kw):
    """Dispatching entry: Pallas on TPU, jnp on the CPU."""
    if jax.default_backend() != "cpu":
        from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_pallas
        return fused_adam_pallas(params, grads, state, **kw)
    return reference_impl(params, grads, state, **kw)


multi_tensor_adam = reference_impl  # parity alias
