"""TP token scatter/gather for MoE blocks.

Parity: reference ``deepspeed/moe/mappings.py`` (adapted there from
Megatron's mpu/mappings.py) — ``gather_tokens`` all-gathers
sequence-partitioned activations over the tensor-parallel group before an
MoE block (whose all-to-all runs over the *expert*-parallel group and must
see full tokens), and ``drop_tokens`` re-partitions them afterwards.  Both
are autograd duals: gather's backward is drop, drop's backward is gather
(the reference's ``_GatherTokens``/``_DropTokens`` autograd functions).

TPU design: ``custom_vjp`` functions built on the comm facade's named-axis
collectives, usable inside ``shard_map`` over the ``tp`` mesh axis.  When no
``tp`` axis is bound (pure-SPMD callers or tp=1) they are the identity, the
analogue of the reference's ``mpu is None`` bail-out (``mappings.py:94``).
"""

from functools import partial

import jax
from jax.lax import axis_size

from deepspeed_tpu.comm import comm


def _tp_bound() -> bool:
    try:
        axis_size("tp")
        return True
    except NameError:
        return False


def _gather(x, dim):
    return comm.all_gather(x, group="tp", axis=dim, tiled=True)


def _drop(x, dim):
    rank = jax.lax.axis_index("tp")
    size = axis_size("tp")
    assert x.shape[dim] % size == 0, (
        f"drop_tokens: dimension {dim} ({x.shape[dim]}) is not divisible "
        f"by tensor parallel world size ({size})")
    chunk = x.shape[dim] // size
    return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=dim)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_tokens(input_, dim):
    return _gather(input_, dim)


def _gather_fwd(input_, dim):
    return _gather(input_, dim), None


def _gather_bwd(dim, _res, g):
    return (_drop(g, dim),)


_gather_tokens.defvjp(_gather_fwd, _gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _drop_tokens(input_, dim):
    return _drop(input_, dim)


def _drop_fwd(input_, dim):
    return _drop(input_, dim), None


def _drop_bwd(dim, _res, g):
    return (_gather(g, dim),)


_drop_tokens.defvjp(_drop_fwd, _drop_bwd)


def gather_tokens(input_, dim: int = 0):
    """All-gather ``input_`` along ``dim`` over the tp axis (reference
    ``gather_tokens``, ``mappings.py:92``); backward drops to this rank's
    chunk.  Identity when no ``tp`` axis is in scope."""
    if not _tp_bound():
        return input_
    return _gather_tokens(input_, dim)


def drop_tokens(input_, dim: int = 0):
    """Keep this tp rank's chunk of ``input_`` along ``dim`` (reference
    ``drop_tokens``, ``mappings.py:98``); backward all-gathers the grads.
    Identity when no ``tp`` axis is in scope."""
    if not _tp_bound():
        return input_
    return _drop_tokens(input_, dim)
