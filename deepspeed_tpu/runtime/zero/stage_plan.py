"""ZeRO stages as sharding plans — the heart of the TPU redesign.

The reference implements ZeRO with imperative machinery: flattened partition
buffers, per-param grad hooks, bucketed reduce-scatter, prefetch hooks
(``stage_1_and_2.py:102``, ``stage3.py:65``, ``partitioned_param_coordinator.py:44``).
On TPU none of that machinery is needed: ZeRO is *a placement policy*, and the
XLA SPMD partitioner materialises the identical communication schedule from
sharding annotations:

========  =================  ==================  ==================
stage     params             gradients           optimizer state
========  =================  ==================  ==================
0 (DDP)   replicated         all-reduce          replicated
1         replicated         all-reduce          fsdp-sharded
2         replicated         reduce-scatter      fsdp-sharded
3 (FSDP)  fsdp-sharded       reduce-scatter      fsdp-sharded
========  =================  ==================  ==================

* "fsdp-sharded": each leaf is sharded on its largest eligible dim over the
  ``fsdp`` mesh axis (flattened-buffer partitioning in the reference; per-dim
  sharding here so XLA can fuse the collectives with compute).
* stage-2 reduce-scatter falls out of constraining grads to the sharded spec:
  the partitioner rewrites all-reduce → reduce-scatter + (lazy) all-gather.
* stage-3 all-gather-on-demand + prefetch (reference param coordinator trace
  machinery): with ``zero_optimization.overlap`` disabled this is left to
  XLA's latency-hiding scheduler over the ``lax.scan`` forward; enabled, it
  is EXPLICIT — :func:`layer_scan` restructures the scan into a
  double-buffered gather pipeline (layer *i+1*'s all-gather issued, and
  pinned by an ``optimization_barrier``, while layer *i* computes), and
  :func:`simulate_forward_schedule` + the interval algebra in
  ``monitor/attribution.py`` make "the gather overlaps compute" a CHECKED
  invariant (tests/unit/test_zero_overlap.py), not a hope.
* ``param_persistence_threshold`` (reference ``zero/config.py``) maps to "keep
  small leaves replicated" — same memory/latency trade.

TP composes: the model provides per-leaf ``PartitionSpec`` rules over the
``tp``/``sp`` axes; the plan adds ``fsdp`` on a free dim.
"""

import contextlib
import contextvars
import functools
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import (DP_AXIS, FSDP_AXIS, SP_AXIS,
                                             TP_AXIS)


def _spec_get(spec: Optional[P], ndim: int):
    """Normalise a PartitionSpec to a per-dim tuple of axis names."""
    if spec is None:
        return [None] * ndim
    entries = list(spec) + [None] * (ndim - len(spec))
    return entries[:ndim]


def _axes_in(entry):
    if entry is None:
        return []
    if isinstance(entry, (tuple, list)):
        return list(entry)
    return [entry]


def add_axis_to_spec(spec: Optional[P], shape, axis_name: str, axis_size: int,
                     mesh_shape=None, prefer_dim: Optional[int] = None) -> P:
    """Return ``spec`` with ``axis_name`` added on the largest eligible dim.

    A dim is eligible when the global extent is divisible by ``axis_size``
    times the product of mesh axes already sharding it.  Falls back to the
    original spec (replicated over ``axis_name``) when nothing divides —
    matching the reference behaviour of leaving un-partitionable tensors whole
    on every rank.
    """
    if axis_size <= 1 or len(shape) == 0:
        return spec if spec is not None else P()
    mesh_shape = mesh_shape or {}
    entries = _spec_get(spec, len(shape))
    candidates = []
    for d, (dim, entry) in enumerate(zip(shape, entries)):
        used = _axes_in(entry)
        if axis_name in used:
            return spec
        existing = 1
        for a in used:
            existing *= mesh_shape.get(a, 1)
        candidates.append((d, dim, existing))
    order = sorted(candidates, key=lambda t: -t[1])
    if prefer_dim is not None:
        order = sorted(order, key=lambda t: (t[0] != prefer_dim, -t[1]))
    for d, dim, existing in order:
        if dim % (axis_size * existing) == 0:
            entry = entries[d]
            if entry is None:
                entries[d] = axis_name
            else:
                entries[d] = tuple(_axes_in(entry) + [axis_name])
            return P(*entries)
    # nothing divides: keep the base spec, truncated to the leaf's rank
    # (a rule written for a 3-D weight may match an auxiliary 1-D leaf,
    # e.g. quantization scales)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _leaf_size(leaf) -> int:
    shape = getattr(leaf, "shape", ())
    return int(np.prod(shape)) if shape else 1


class ZeroShardingPlan:
    """Produces NamedShardings for params / grads / optimizer state / batch.

    ``tp_rules``: optional list of ``(path_regex, PartitionSpec)`` supplying
    tensor/sequence-parallel specs per parameter (the model's sharding map).
    """

    def __init__(self, mesh, stage: int = 0,
                 tp_rules=None,
                 param_persistence_threshold: int = 0,
                 offload_optimizer: bool = False,
                 offload_param: bool = False):
        assert stage in (0, 1, 2, 3)
        self.mesh = mesh
        self.stage = stage
        self.tp_rules = [(re.compile(pat), spec) for pat, spec in (tp_rules or [])]
        self.param_persistence_threshold = param_persistence_threshold
        self.offload_optimizer = offload_optimizer
        self.offload_param = offload_param
        self.fsdp_size = mesh.shape.get(FSDP_AXIS, 1)

    # ------------------------------------------------------------------
    def _tp_spec_for(self, path: str, leaf) -> Optional[P]:
        for pat, spec in self.tp_rules:
            if pat.search(path):
                return spec
        return None

    def _fsdp_spec(self, path: str, leaf) -> P:
        """Full stage-3 spec: tp spec + fsdp on a free dim."""
        base = self._tp_spec_for(path, leaf)
        if self._leaf_persists(leaf):
            return base if base is not None else P()
        return add_axis_to_spec(base, getattr(leaf, "shape", ()),
                                FSDP_AXIS, self.fsdp_size,
                                mesh_shape=dict(self.mesh.shape))

    def _replicated_spec(self, path: str, leaf) -> P:
        base = self._tp_spec_for(path, leaf)
        return base if base is not None else P()

    def _leaf_persists(self, leaf) -> bool:
        # small tensors stay replicated (reference param_persistence_threshold)
        return _leaf_size(leaf) < self.param_persistence_threshold

    # ------------------------------------------------------------------
    # Public: spec pytrees (for with_sharding_constraint) and sharding
    # pytrees (for jit in/out shardings + device_put)
    # ------------------------------------------------------------------
    def param_specs(self, params) -> Any:
        fn = self._fsdp_spec if self.stage >= 3 else self._replicated_spec
        return self._map_with_path(fn, params)

    def grad_specs(self, params) -> Any:
        fn = self._fsdp_spec if self.stage >= 2 else self._replicated_spec
        return self._map_with_path(fn, params)

    def master_param_specs(self, params) -> Any:
        """fp32 master copies partition like optimizer state from stage 1 up
        (reference: stage-1 partitions the fp32 flat buffer)."""
        fn = self._fsdp_spec if self.stage >= 1 else self._replicated_spec
        return self._map_with_path(fn, params)

    def opt_state_specs(self, tx, params) -> Any:
        """Optimizer-state specs aligned leaf-for-leaf with params via
        ``optax.tree_map_params``; non-param leaves (step counts) replicate."""
        import optax
        opt_shape = jax.eval_shape(tx.init, params)
        pspecs = self.master_param_specs(params)
        return optax.tree_map_params(
            tx, lambda _, spec: spec, opt_shape, pspecs,
            transform_non_params=lambda _: P())

    def batch_spec(self, ndim: int = 2, sequence_dim: Optional[int] = None) -> P:
        """Batch dim sharded over every data axis (incl. ep — EP overlays DP);
        optional sequence dim over ``sp`` (Ulysses input layout)."""
        from deepspeed_tpu.parallel.topology import BATCH_AXES
        entries = [None] * ndim
        entries[0] = tuple(BATCH_AXES)
        sp = self.mesh.shape.get(SP_AXIS, 1)
        if sequence_dim is not None and sp > 1:
            entries[sequence_dim] = SP_AXIS
        return P(*entries)

    # sharding (NamedSharding) versions --------------------------------
    def _to_sharding(self, spec_tree):
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    def param_shardings(self, params):
        return self._to_sharding(self.param_specs(params))

    def grad_shardings(self, params):
        return self._to_sharding(self.grad_specs(params))

    def opt_state_shardings(self, tx, params):
        return self._to_sharding(self.opt_state_specs(tx, params))

    def batch_sharding(self, ndim=2, sequence_dim=None):
        return NamedSharding(self.mesh, self.batch_spec(ndim, sequence_dim))

    def replicated_sharding(self):
        return NamedSharding(self.mesh, P())

    # ------------------------------------------------------------------
    @staticmethod
    def _map_with_path(fn, tree):
        def wrap(path, leaf):
            return fn(jax.tree_util.keystr(path), leaf)
        return jax.tree_util.tree_map_with_path(wrap, tree)


def device_put_global(tree, shardings):
    """``jax.device_put`` that also works on multi-host meshes.

    ``device_put`` refuses shardings with non-addressable devices; on a pod
    every process holds the same host value (SPMD init), so the global
    array is assembled per-device from the host copy
    (``make_array_from_callback`` hands each local device its slice —
    the single-controller path stays a plain device_put)."""
    def put(x, sh):
        if sh is None:
            return x
        if jax.process_count() == 1 or sh.is_fully_addressable:
            return jax.device_put(x, sh)
        host = np.asarray(jax.device_get(x)) if isinstance(x, jax.Array) \
            else np.asarray(x)
        return jax.make_array_from_callback(
            host.shape, sh, lambda idx: host[idx])
    return jax.tree_util.tree_map(put, tree, shardings)


def active_mesh():
    """The ambient mesh installed by ``with mesh:`` — None outside."""
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def maybe_constrain(x, spec: P):
    """with_sharding_constraint that is a no-op outside a mesh context (so
    model code runs unsharded in plain tests/inference)."""
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain(tree, spec_tree, mesh):
    """with_sharding_constraint over a pytree of PartitionSpecs.

    Uses flatten_up_to so it is robust to PartitionSpec's own pytree
    registration (P must be treated as a leaf of ``spec_tree``).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec_leaves = treedef.flatten_up_to(spec_tree)
    out = [jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))
           for x, s in zip(leaves, spec_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


# ----------------------------------------------------------------------
# Explicit comm/compute overlap (``zero_optimization.overlap``)
# ----------------------------------------------------------------------
# FROZEN overlap gauge vocabulary — the engine's per-step overlap
# telemetry.  Mirrored byte-for-byte in scripts/check_telemetry_schema.py
# (OVERLAP_GAUGES there) with a lockstep test; extend both together.
OVERLAP_GAUGES = (
    "comm/overlap/exposed_ms",
    "comm/overlap/overlapped_ms",
    "comm/overlap/gather_buckets",
    "comm/overlap/rs_buckets",
    "comm/overlap/prefetch_depth",
)


class OverlapContext:
    """Trace-scope state for :func:`layer_scan`'s gather pipeline.

    Installed by :func:`overlap_scope` (the engine wraps its step builder
    in one, so the context is live exactly while jit traces the step —
    retraces included).  Carries the config knobs plus an optional
    ``spec_fn(path, stacked_leaf) -> PartitionSpec`` returning the BASE
    (tensor-parallel) spec of each stacked leaf: the gather target for a
    layer slice is that spec minus the leading layer dim — i.e. gather
    over ``fsdp`` only, leaving Megatron TP partitioning (and therefore
    the compute math) untouched.  ``on_gather(nbytes, n_layers)`` is the
    trace-time comm-census hook.  The ``layers``/``gathered_bytes``/...
    attributes are filled in at trace time by the last pipelined scan and
    read back by the engine's telemetry tail."""

    def __init__(self, gather_prefetch_depth: int = 1,
                 param_persistence_threshold: int = 0,
                 spec_fn=None, on_gather=None):
        self.gather_prefetch_depth = max(1, int(gather_prefetch_depth))
        self.param_persistence_threshold = int(param_persistence_threshold)
        self.spec_fn = spec_fn
        self.on_gather = on_gather
        # trace-time stats of the most recent pipelined scan
        self.scans = 0
        self.layers = 0
        self.gathered_bytes = 0
        self.pipelined_leaves = 0
        self.persistent_leaves = 0


_OVERLAP: contextvars.ContextVar = contextvars.ContextVar(
    "zero_overlap", default=None)


def current_overlap() -> Optional[OverlapContext]:
    """The ambient :class:`OverlapContext`, or None (serial scan)."""
    return _OVERLAP.get()


@contextlib.contextmanager
def overlap_scope(ctx: Optional[OverlapContext]):
    """Install ``ctx`` for the duration of the block (None = serial)."""
    token = _OVERLAP.set(ctx)
    try:
        yield ctx
    finally:
        _OVERLAP.reset(token)


@jax.custom_vjp
def _pin(pair):
    """``optimization_barrier`` with an identity gradient.

    JAX ships no differentiation rule for the barrier primitive, and the
    pipeline must be differentiable (the gather runs inside the model
    forward).  The barrier pins collective ISSUE ORDER on the primal
    path; autodiff sees a plain identity, so cotangents flow through
    untouched — values and grads stay bit-identical."""
    return jax.lax.optimization_barrier(pair)


def _pin_fwd(pair):
    return jax.lax.optimization_barrier(pair), None


def _pin_bwd(_, ct):
    return (ct,)


_pin.defvjp(_pin_fwd, _pin_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_to(x, sharding):
    """``with_sharding_constraint`` on the PRIMAL path only.

    Differentiating through a sharding constraint annotates the
    cotangent with the same (gathered) sharding, which steers the SPMD
    partitioner toward an all-reduce-to-replicated gradient for the
    slice where the serial scan leaves the choice (typically a direct
    reduce-scatter into the layer-sharded stacked leaf) to the cost
    model.  Different collective, different summation grouping, ulp
    drift.  A forward-only annotation moves the gather's issue point
    without touching how backward partitions — the whole point of the
    overlap layer ("reorder communication, never math")."""
    return jax.lax.with_sharding_constraint(x, sharding)


def _gather_to_fwd(x, sharding):
    return jax.lax.with_sharding_constraint(x, sharding), None


def _gather_to_bwd(sharding, _, ct):
    return (ct,)


_gather_to.defvjp(_gather_to_fwd, _gather_to_bwd)


def _slice_gather_spec(base_spec: Optional[P], stacked_ndim: int) -> P:
    """Gather target for one layer slice of a stacked ``[L, ...]`` leaf:
    the stacked leaf's base (TP) spec with the leading layer dim dropped.
    No ``fsdp`` entry ever appears (the plan adds fsdp on top of the base
    spec), so constraining a slice to this spec is exactly "all-gather
    the ZeRO-3 shards, keep the TP split"."""
    entries = _spec_get(base_spec, stacked_ndim)[1:]
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def layer_scan(body, init, xs, length=None):
    """``jax.lax.scan`` over stacked layers, with an optional explicit
    parameter-gather pipeline (``zero_optimization.overlap``).

    With no :func:`overlap_scope` active this IS ``jax.lax.scan(body,
    init, xs)`` — bit-for-bit the seed forward.  Under an active context
    the scan is restructured into a double-buffered prefetch pipeline
    with ``depth = gather_prefetch_depth``:

    * ``depth`` per-layer working sets ("buffers") ride the carry;
      buffer rotation is donation-safe (XLA aliases the slots in the
      loop body — no per-iteration allocation).
    * pipelined leaves are delivered through the scan's NATIVE xs
      mechanism, but rotated ``depth`` layers ahead (``jnp.roll(leaf,
      -depth, axis=0)``): iteration *k* receives layer ``k + depth``'s
      slice, constrains it to the slice's replicated-over-fsdp spec (the
      explicit all-gather), and parks it in the buffer queue while the
      body consumes layer *k*'s slice from the queue head.  An
      ``optimization_barrier`` ties the fresh gather to the consumed
      buffer, pinning its issue point UNDER layer *k*'s compute where
      XLA's latency-hiding scheduler may or may not have put it.
    * small slices (``param_persistence_threshold``) skip the pipe:
      persistent leaves stay on the unrotated xs path, exactly as in the
      serial scan.

    Math is untouched — and the STRUCTURE of the backward pass is the
    serial scan-transpose, which is what makes the trajectory
    bit-identical rather than merely close: because slices ride the
    native xs path, each layer's parameter cotangent is produced by the
    very same in-loop transpose machinery (same dot, same
    reduce/scatter placement) as the serial scan, lands in the rotated
    grad stack, and is un-rotated by the transpose of ``roll`` — a pure
    permutation (``collective-permute``), no arithmetic.  The wrapped
    tail deliveries (layers ``0..depth-1`` arriving at iterations
    ``L-depth..L-1``) are never consumed, so their cotangent rows are
    zero; the prefill gathers (issued before the loop) carry those
    layers' cotangents instead, and the two accumulate by ``x + 0``
    adds.  Only the gathers' ISSUE POINTS move; per-layer values and
    parameter gradients are bit-identical to the serial scan (checked in
    tests/unit/test_zero_overlap.py).  One caveat survives at the full
    engine level: the SPMD partitioner may STAGE a multi-axis grad
    all-reduce differently between the two programs (flat vs
    grouped-per-axis), which reorders the same cross-rank sum at the
    ulp level — its own communication reordering, outside this
    transform's control.
    """
    ctx = current_overlap()
    leaves = jax.tree_util.tree_leaves(xs)
    if ctx is None or not leaves:
        return jax.lax.scan(body, init, xs, length=length)
    n_layers = int(leaves[0].shape[0])
    depth = ctx.gather_prefetch_depth
    if n_layers <= 1:
        return jax.lax.scan(body, init, xs, length=length)
    mesh = active_mesh()
    thresh = ctx.param_persistence_threshold

    # per-leaf gather specs (None = persistent slice, skip the pipeline)
    flat, treedef = jax.tree_util.tree_flatten(xs)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(xs)[0]]
    gather_specs = []
    gathered_bytes = 0
    for path, leaf in zip(paths, flat):
        slice_size = _leaf_size(leaf) // n_layers
        if slice_size < thresh or mesh is None:
            gather_specs.append(None)
            continue
        base = ctx.spec_fn(path, leaf) if ctx.spec_fn is not None else None
        gather_specs.append(_slice_gather_spec(base, leaf.ndim))
        gathered_bytes += slice_size * np.dtype(leaf.dtype).itemsize
    ctx.scans += 1
    ctx.layers = n_layers
    ctx.gathered_bytes = gathered_bytes * n_layers
    ctx.pipelined_leaves = sum(1 for s in gather_specs if s is not None)
    ctx.persistent_leaves = sum(1 for s in gather_specs if s is None)
    if ctx.on_gather is not None and ctx.pipelined_leaves:
        ctx.on_gather(ctx.gathered_bytes, n_layers)
    if ctx.pipelined_leaves == 0:
        return jax.lax.scan(body, init, xs, length=length)

    # a prefetch deeper than L-1 gathers nothing new
    depth = min(depth, n_layers - 1)
    pipe_idx = [i for i, s in enumerate(gather_specs) if s is not None]

    def constrain(i, x):
        return _gather_to(x, NamedSharding(mesh, gather_specs[i]))

    def prefill(k):
        """Layer ``k``'s pipelined slices, gathered before the loop."""
        return tuple(
            constrain(i, jax.lax.dynamic_index_in_dim(
                flat[i], k, 0, keepdims=False))
            for i in pipe_idx)

    # pipelined leaves rotate depth layers ahead on the xs path;
    # persistent leaves stay put (bitwise the serial delivery)
    shifted = [jnp.roll(leaf, -depth, axis=0) if gather_specs[i] is not None
               else leaf for i, leaf in enumerate(flat)]
    bufs = tuple(prefill(i) for i in range(depth))

    def step(carry, xk):
        state, bufs = carry
        # xk's pipelined slices are layer k+depth's: constrain = gather
        nxt = tuple(constrain(i, xk[i]) for i in pipe_idx)
        # the barrier ties layer k+depth's gather to layer k's input:
        # the gather must be ISSUED before the body that consumes cur
        # can retire, i.e. it runs under layer k's compute
        cur, nxt = _pin((bufs[0], nxt))
        merged = list(xk)
        for slot, i in enumerate(pipe_idx):
            merged[i] = cur[slot]
        state, y = body(state, jax.tree_util.tree_unflatten(treedef, merged))
        return (state, bufs[1:] + (nxt,)), y

    (state, _), ys = jax.lax.scan(step, (init, bufs), tuple(shifted))
    return state, ys


def _leaf_nbytes(leaf) -> int:
    return _leaf_size(leaf) * np.dtype(leaf.dtype).itemsize


def plan_reduce_buckets(leaves, bucket_bytes: int):
    """Partition grad-leaf indices into reduce-scatter buckets.

    Buckets are filled in REVERSE flatten order — the last layers' grads
    are final first during backward, so flushing them first lets each
    bucket's reduction overlap the backward compute of earlier layers
    (the reference's registration-order-reversed IPG bucketing,
    ``stage3.py __reduce_and_partition_ipg_grads``).  Every bucket holds
    at least one leaf; a single leaf larger than ``bucket_bytes`` gets a
    bucket of its own."""
    buckets, cur, cur_bytes = [], [], 0
    for i in reversed(range(len(leaves))):
        nb = _leaf_nbytes(leaves[i])
        if cur and cur_bytes + nb > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        buckets.append(cur)
    return buckets


def simulate_forward_schedule(n_layers: int, compute_ms: float,
                              gather_ms: float, prefetch_depth: int = 0):
    """Analytic schedule of the scan-forward gather pipeline.

    Models exactly what :func:`layer_scan` emits: ``prefetch_depth = 0``
    is the serial schedule (gather k, then compute k, back to back — the
    seed's worst case, where nothing overlaps); ``depth >= 1`` issues
    gather *k* at the start of iteration ``k - depth`` with the comm
    channel serializing gathers.  Returns the ``comm``/``compute``
    interval lists (seconds — feed them to ``decompose_step`` or the
    interval algebra directly) plus the derived exposure:

    * serial: ``exposed_comm_frac = g / (g + c)``
    * depth >= 1, ``g <= c``: only the prefill gather is exposed —
      ``exposed_comm_frac = g / (g + L*c)``

    tests/unit/test_zero_overlap.py holds the layer_scan docstring to
    this model, and an explicit 4-rank ``shard_map`` run of both
    schedules to the same bits."""
    g = float(gather_ms) / 1000.0
    c = float(compute_ms) / 1000.0
    comm, compute = [], []
    if prefetch_depth <= 0:
        t = 0.0
        for _ in range(n_layers):
            comm.append((t, t + g))
            compute.append((t + g, t + g + c))
            t += g + c
    else:
        depth = int(prefetch_depth)
        comp_start = [0.0] * n_layers
        prev_comm_end = prev_comp_end = 0.0
        for k in range(n_layers):
            ready = prev_comm_end if k < depth else \
                max(prev_comm_end, comp_start[k - depth])
            comm.append((ready, ready + g))
            prev_comm_end = ready + g
            comp_start[k] = max(prev_comp_end, prev_comm_end)
            compute.append((comp_start[k], comp_start[k] + c))
            prev_comp_end = comp_start[k] + c
    from deepspeed_tpu.monitor.attribution import (overlap_length,
                                                   total_length)
    step_s = compute[-1][1] if compute else 0.0
    exposed_s = total_length(comm) - overlap_length(comm, compute)
    return {
        "comm": comm,
        "compute": compute,
        "step_ms": step_s * 1000.0,
        "comm_ms": total_length(comm) * 1000.0,
        "exposed_comm_ms": exposed_s * 1000.0,
        "exposed_comm_frac": exposed_s / step_s if step_s > 0 else 0.0,
    }
