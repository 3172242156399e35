"""Quantized collectives — blockwise-int8 wire codec for bandwidth-bound paths.

EQuARX (PAPERS.md, arXiv:2506.17615) shows that int8-quantizing both wire
phases of an XLA all-reduce recovers most of the collective bandwidth at
negligible quality cost.  This module is the repo's one home for that codec:

* :func:`blockwise_quantize` / :func:`blockwise_dequantize` — symmetric
  per-block absmax int8 with an fp32 scale sidecar (one scale per
  ``block_size`` elements).
* :func:`quantized_all_reduce` — the two-phase EQuARX shape inside
  ``shard_map``: reduce-scatter int8 chunks + fp32 scales, dequantize and
  sum locally in fp32, re-quantize, all-gather.
* :func:`quantized_reduce_scatter` — phase 1 alone, returning this rank's
  reduced chunk (the ZeRO stage ≥ 2 grad-reduce verb).
* :class:`CommQuantizer` — config-driven selection with dtype-aware
  fallback (integer tensors, tiny tensors, and non-listed verbs pass
  through untouched) plus the host-side payload codec used by the
  disaggregated-fleet KV-page migration transport.
* :data:`SCHEMES` — the compression-scheme registry unifying this codec
  with the existing 1-bit error-feedback path in
  ``runtime/comm_compression.py`` (``none | int8_block | onebit``).

The engine's grad path is trace-level SPMD: XLA inserts the physical
reduce-scatter from sharding constraints, so the training hot path models
the wire codec as a blockwise quantize-dequantize (QDQ) of the gradient —
exactly the phase-2 re-quantization of the two-phase collective (phase-1
per-rank error averages down by 1/world).  The REAL shard_map collectives
here are what a multi-chip deployment lowers to, and are what the unit
tests (``tests/unit/test_comm_quant.py``) exercise directly.
"""

from dataclasses import dataclass
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Verbs the codec knows how to carry.  ``kv_migrate`` is the fleet KV-page
# transport (host-side payload codec, not a lax collective).
QUANTIZABLE_VERBS = ("all_reduce", "reduce_scatter", "kv_migrate")

# Compression-scheme registry vocabulary (see SCHEMES below).
QUANT_SCHEMES = ("none", "int8_block", "onebit")

# Frozen gauge vocabulary — mirrored byte-for-byte in
# scripts/check_telemetry_schema.py with a lockstep test.  One gauge per
# quantizable wire path; emitted by Telemetry.collective() when a census
# entry carries bytes_saved.
QUANT_GAUGES = (
    "comm/all_reduce/quant_bytes_saved",
    "comm/reduce_scatter/quant_bytes_saved",
    "comm/kv_migrate/quant_bytes_saved",
)

_INT8_MAX = 127.0


# ----------------------------------------------------------------------
# blockwise codec
# ----------------------------------------------------------------------


def blockwise_quantize(x: jnp.ndarray, block_size: int = 256,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-block absmax int8: flat ``x`` (numel divisible by
    ``block_size``) → ``(codes int8 [nblocks, block], scales fp32
    [nblocks, 1])``.  Zero blocks get scale 1.0 so dequantize is exact."""
    g = x.astype(jnp.float32).reshape(-1, block_size)
    scale = jnp.max(jnp.abs(g), axis=1, keepdims=True) / _INT8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    codes = jnp.clip(jnp.round(g / scale), -128, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def blockwise_dequantize(codes: jnp.ndarray, scales: jnp.ndarray
                         ) -> jnp.ndarray:
    """Inverse of :func:`blockwise_quantize`; returns flat fp32."""
    return (codes.astype(jnp.float32) * scales).reshape(-1)


def blockwise_qdq(x: jnp.ndarray, block_size: int = 256) -> jnp.ndarray:
    """Quantize-dequantize round trip preserving shape and dtype — the
    trace-level model of one wire phase of the quantized collective."""
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    codes, scales = blockwise_quantize(flat, block_size)
    out = blockwise_dequantize(codes, scales)[:n]
    return out.reshape(x.shape).astype(x.dtype)


# ----------------------------------------------------------------------
# shard_map collectives (the real wire shape)
# ----------------------------------------------------------------------


def quantized_all_reduce(x: jnp.ndarray, axis_name: str,
                         block_size: int = 256) -> jnp.ndarray:
    """Two-phase EQuARX all-reduce (SUM) of a flat vector inside
    ``shard_map``: phase 1 scatters int8 chunks + fp32 scales
    (the reduce-scatter wire phase), each rank dequantizes its chunk's
    ``world`` versions and sums in fp32, re-quantizes, and phase 2
    all-gathers int8 + scales.  ``numel`` must be divisible by
    ``world * block_size`` (pad upstream with :func:`pad_for_world`)."""
    world = lax.psum(1, axis_name)
    n = x.shape[0]
    chunk = n // world

    codes, scales = blockwise_quantize(x.astype(jnp.float32), block_size)
    codes = codes.reshape(world, chunk // block_size, block_size)
    scales = scales.reshape(world, chunk // block_size, 1)
    recv_c = lax.all_to_all(codes, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    recv_s = lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    mine = jax.vmap(blockwise_dequantize)(
        recv_c.reshape(world, -1, block_size),
        recv_s.reshape(world, -1, 1)).sum(axis=0)

    out_c, out_s = blockwise_quantize(mine, block_size)
    all_c = lax.all_gather(out_c, axis_name)
    all_s = lax.all_gather(out_s, axis_name)
    return jax.vmap(blockwise_dequantize)(all_c, all_s).reshape(-1)


def quantized_reduce_scatter(x: jnp.ndarray, axis_name: str,
                             block_size: int = 256) -> jnp.ndarray:
    """Phase 1 alone: scatter int8 chunks + scales, dequantize-sum this
    rank's chunk in fp32.  Returns the rank-local reduced chunk of length
    ``numel // world`` — the ZeRO stage ≥ 2 grad-reduce verb."""
    world = lax.psum(1, axis_name)
    n = x.shape[0]
    chunk = n // world

    codes, scales = blockwise_quantize(x.astype(jnp.float32), block_size)
    codes = codes.reshape(world, chunk // block_size, block_size)
    scales = scales.reshape(world, chunk // block_size, 1)
    recv_c = lax.all_to_all(codes, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    recv_s = lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    return jax.vmap(blockwise_dequantize)(
        recv_c.reshape(world, -1, block_size),
        recv_s.reshape(world, -1, 1)).sum(axis=0)


def pad_for_world(x: jnp.ndarray, world: int, block_size: int = 256):
    """Pad flat ``x`` so ``numel % (world * block_size) == 0``; returns
    ``(padded, original_numel)``."""
    n = x.shape[0]
    rem = (-n) % (world * block_size)
    if rem == 0:
        return x, n
    return jnp.concatenate([x, jnp.zeros((rem,), x.dtype)]), n


# ----------------------------------------------------------------------
# analytic wire accounting
# ----------------------------------------------------------------------


def quant_payload_bytes(numel: int, block_size: int = 256) -> int:
    """One wire phase of the codec: int8 codes + fp32 per-block scales."""
    nblocks = -(-numel // block_size)
    return numel + nblocks * 4


def quant_bytes_saved(numel: int, dtype: Any, block_size: int = 256) -> int:
    """Payload bytes saved vs the dtype-true baseline the comm census
    books (``numel * itemsize``).  Both phases of the two-phase collective
    shrink by the same ratio, so one-phase payload accounting keeps the
    census's existing size semantics.  Clamped at 0 (a ≤1-byte dtype
    cannot save wire bytes through this codec)."""
    baseline = numel * jnp.dtype(dtype).itemsize
    return max(0, baseline - quant_payload_bytes(numel, block_size))


# ----------------------------------------------------------------------
# config-driven selection + host payload codec
# ----------------------------------------------------------------------


@dataclass
class QuantizedLeaf:
    """One quantized pytree leaf of a host-side payload."""
    codes: Any            # int8 [nblocks, block]
    scales: Any           # fp32 [nblocks, 1]
    shape: Tuple[int, ...]
    dtype: Any            # original leaf dtype (restored on decode)
    numel: int


@dataclass
class QuantizedPayload:
    """Self-describing quantized wrapper around a migrated pytree: the
    receiver needs no config to decode.  ``leaves`` mixes QuantizedLeaf
    (float leaves) and raw arrays (fallback leaves)."""
    leaves: Any           # pytree with QuantizedLeaf at quantized positions
    block_size: int
    wire_bytes: int       # payload bytes actually on the wire
    raw_bytes: int        # dtype-true bytes the unquantized payload had

    @property
    def bytes_saved(self) -> int:
        return max(0, self.raw_bytes - self.wire_bytes)

    def to_wire(self) -> dict:
        """Versioned JSON-safe envelope for the cross-process fleet
        transport (``inference/transport.py``).  Codes stay int8 on the
        wire — serialization preserves the codec's byte saving."""
        from deepspeed_tpu.inference.transport import payload_to_wire
        return payload_to_wire(self)

    @staticmethod
    def from_wire(d: dict):
        """Inverse of :meth:`to_wire`; rejects an unknown major wire
        version with the typed ``WireVersionError``.  Also accepts (and
        passes through) the raw-payload envelope, mirroring
        :meth:`CommQuantizer.decode_payload`'s raw passthrough."""
        from deepspeed_tpu.inference.transport import payload_from_wire
        return payload_from_wire(d)


def _is_quantized_leaf(x) -> bool:
    return isinstance(x, QuantizedLeaf)


@dataclass
class CommQuantizer:
    """Config-backed policy: which verbs/tensors ride the int8 codec.

    Mirrors the ``comm.quantization`` config block; ``select`` and the
    codec helpers implement the dtype-aware fallback — integer tensors,
    tensors under ``min_tensor_bytes``, and verbs not in ``verbs`` pass
    through untouched.
    """
    enabled: bool = False
    scheme: str = "int8_block"
    dtype: str = "int8"
    block_size: int = 256
    min_tensor_bytes: int = 1024
    verbs: Sequence[str] = QUANTIZABLE_VERBS

    @classmethod
    def from_config(cls, cfg) -> "CommQuantizer":
        """Build from a ``comm.quantization`` mapping or config model
        (anything with the block's attribute names); None → disabled."""
        if cfg is None:
            return cls(enabled=False)
        if isinstance(cfg, dict):
            cfg = dict(cfg)
            get = cfg.get
        else:
            get = lambda k, d=None: getattr(cfg, k, d)  # noqa: E731
        return cls(
            enabled=bool(get("enabled", False)),
            scheme=str(get("scheme", "int8_block")),
            dtype=str(get("dtype", "int8")),
            block_size=int(get("block_size", 256)),
            min_tensor_bytes=int(get("min_tensor_bytes", 1024)),
            verbs=tuple(get("verbs", QUANTIZABLE_VERBS)),
        )

    # -- selection ------------------------------------------------------

    def active(self) -> bool:
        return self.enabled and self.scheme == "int8_block"

    def should_quantize(self, dtype: Any, nbytes: int, verb: str) -> bool:
        """The fallback policy, in one place: every wiring site asks this
        before touching a tensor."""
        if not self.active() or verb not in self.verbs:
            return False
        if nbytes < self.min_tensor_bytes:
            return False
        dt = jnp.dtype(dtype) if not isinstance(dtype, jnp.dtype) else dtype
        if not jnp.issubdtype(dt, jnp.floating):
            return False
        # int8 codes + fp32 scales must actually be smaller on the wire
        return dt.itemsize > 1

    # -- trace-level grad codec (engine wiring) -------------------------

    def qdq_tree(self, tree, verb: str):
        """Apply the wire QDQ to every qualifying leaf of a grad tree;
        non-qualifying leaves pass through untouched.  Returns
        ``(tree, bytes_saved)`` where bytes_saved is the analytic payload
        saving summed over quantized leaves (0 when nothing qualified)."""
        saved = 0

        def leaf(g):
            nonlocal saved
            nbytes = g.size * jnp.dtype(g.dtype).itemsize
            if not self.should_quantize(g.dtype, nbytes, verb):
                return g
            saved += quant_bytes_saved(g.size, g.dtype, self.block_size)
            return blockwise_qdq(g, self.block_size)

        return jax.tree_util.tree_map(leaf, tree), saved

    def tree_bytes_saved(self, tree, verb: str) -> int:
        """Analytic payload saving for a tree without transforming it."""
        saved = 0
        for g in jax.tree_util.tree_leaves(tree):
            nbytes = g.size * jnp.dtype(g.dtype).itemsize
            if self.should_quantize(g.dtype, nbytes, verb):
                saved += quant_bytes_saved(g.size, g.dtype, self.block_size)
        return saved

    # -- host payload codec (fleet KV migration) ------------------------

    def encode_payload(self, payload, verb: str = "kv_migrate"):
        """Quantize a host pytree for the wire.  Returns the payload
        unchanged when the policy says no leaf qualifies (so disabled
        configs are bit-for-bit the current transport); otherwise a
        :class:`QuantizedPayload`.  Content addressing (dedup chain keys)
        must be computed by the caller BEFORE encoding."""
        if not self.active() or verb not in self.verbs:
            return payload
        wire = raw = quantized = 0

        def enc(leaf):
            nonlocal wire, raw, quantized
            arr = jnp.asarray(leaf)
            nbytes = arr.size * jnp.dtype(arr.dtype).itemsize
            raw += nbytes
            if not self.should_quantize(arr.dtype, nbytes, verb):
                wire += nbytes
                return arr
            flat = arr.astype(jnp.float32).reshape(-1)
            pad = (-flat.shape[0]) % self.block_size
            if pad:
                flat = jnp.pad(flat, (0, pad))
            codes, scales = blockwise_quantize(flat, self.block_size)
            wire += quant_payload_bytes(arr.size, self.block_size)
            quantized += 1
            return QuantizedLeaf(codes=codes, scales=scales,
                                 shape=tuple(arr.shape), dtype=arr.dtype,
                                 numel=arr.size)

        leaves = jax.tree_util.tree_map(enc, payload)
        if quantized == 0:
            return payload
        return QuantizedPayload(leaves=leaves, block_size=self.block_size,
                                wire_bytes=wire, raw_bytes=raw)

    @staticmethod
    def decode_payload(payload):
        """Inverse of :func:`encode_payload`; raw payloads pass through."""
        if not isinstance(payload, QuantizedPayload):
            return payload

        def dec(leaf):
            if not _is_quantized_leaf(leaf):
                return leaf
            flat = blockwise_dequantize(leaf.codes, leaf.scales)[:leaf.numel]
            return flat.reshape(leaf.shape).astype(leaf.dtype)

        return jax.tree_util.tree_map(dec, payload.leaves,
                                      is_leaf=_is_quantized_leaf)


# ----------------------------------------------------------------------
# compression-scheme registry (none | int8_block | onebit)
# ----------------------------------------------------------------------


@dataclass(frozen=True)
class CompressionScheme:
    """Registry record: a wire codec's shard_map all-reduce and its
    analytic per-rank wire-byte model."""
    name: str
    allreduce: Any        # callable(x, axis_name, **kw) or None for "none"
    wire_bytes: Any       # callable(numel, world, **kw) -> int


def _none_bytes(numel: int, world: int, dtype_bytes: int = 4, **_):
    # ring all-reduce payload: ~2 phases of the full vector
    return 2 * numel * dtype_bytes


def _int8_block_bytes(numel: int, world: int, block_size: int = 256, **_):
    # phase 1 scatters the full quantized vector; phase 2 gathers world
    # quantized chunks of numel/world each
    world = max(world, 1)
    return (quant_payload_bytes(numel, block_size)
            + quant_payload_bytes(numel // world, block_size) * world)


def _onebit_allreduce(x, axis_name, **kw):
    from deepspeed_tpu.runtime import comm_compression as cc
    world_err = kw.pop("worker_error")
    server_err = kw.pop("server_error")
    return cc.compressed_allreduce(x, world_err, server_err, axis_name)


def _onebit_bytes(numel: int, world: int, **_):
    from deepspeed_tpu.runtime import comm_compression as cc
    return cc.compressed_allreduce_bytes(numel, world)


SCHEMES = {
    "none": CompressionScheme("none", None, _none_bytes),
    "int8_block": CompressionScheme("int8_block", quantized_all_reduce,
                                    _int8_block_bytes),
    "onebit": CompressionScheme("onebit", _onebit_allreduce, _onebit_bytes),
}


def get_scheme(name: str) -> CompressionScheme:
    if name not in SCHEMES:
        raise ValueError(
            f"unknown compression scheme {name!r}; expected one of "
            f"{sorted(SCHEMES)}")
    return SCHEMES[name]
