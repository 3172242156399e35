"""Ulysses-style sequence parallelism (all-to-all head↔sequence swap).

The reference has NO sequence parallelism (SURVEY §2.4: absent in 0.8.3);
this fills the gap the TPU-first way, as DeepSpeed later did with
"DeepSpeed-Ulysses": attention inputs arrive sequence-sharded over the ``sp``
axis; an all-to-all re-shards them head-wise so every device computes full
-sequence attention for ``H/sp`` heads; a second all-to-all restores the
sequence sharding.  Both all-to-alls ride ICI and cost O(S·D/sp) per device.
"""

import jax
import jax.numpy as jnp
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import BATCH_AXES, SP_AXIS
from deepspeed_tpu.runtime.zero.stage_plan import active_mesh


def sp_degree(mesh=None) -> int:
    mesh = mesh or active_mesh()
    if mesh is None:
        return 1
    return mesh.shape.get(SP_AXIS, 1)


def _seq_to_heads(x, axis_name):
    """[B, S/sp, H, D] → [B, S, H/sp, D] via all-to-all."""
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def _heads_to_seq(x, axis_name):
    """[B, S, H/sp, D] → [B, S/sp, H, D]."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention_local(q, k, v, attn_fn, axis_name=SP_AXIS):
    """Per-device body (call inside shard_map): q/k/v sequence-sharded
    [B, S/sp, H, D]; ``attn_fn(q,k,v)`` computes full attention on the
    head-sharded views."""
    sp = axis_size(axis_name)
    H = q.shape[2]
    Hkv = k.shape[2]
    assert H % sp == 0, f"n_heads {H} must divide sp degree {sp}"
    assert Hkv % sp == 0, f"n_kv_heads {Hkv} must divide sp degree {sp}"
    q = _seq_to_heads(q, axis_name)
    k = _seq_to_heads(k, axis_name)     # stays at Hkv/sp heads (GQA-aware)
    v = _seq_to_heads(v, axis_name)
    out = attn_fn(q, k, v)              # [B, S, H/sp, D]
    return _heads_to_seq(out, axis_name)


def ulysses_attention(q, k, v, attn_fn, mesh=None):
    """GSPMD entry: q/k/v are global [B, S, H, D] arrays (sequence-sharded
    over ``sp`` by the activation layout); runs the shard_map body over the
    mesh.  Falls back to plain attention when sp degree is 1."""
    mesh = mesh or active_mesh()
    if mesh is None or mesh.shape.get(SP_AXIS, 1) == 1:
        return attn_fn(q, k, v)
    spec = P(tuple(BATCH_AXES), SP_AXIS, None, None)
    body = jax.shard_map(
        lambda q, k, v: ulysses_attention_local(q, k, v, attn_fn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return body(q, k, v)
