"""Sharded MoE: gating + expert dispatch.

Parity: reference ``deepspeed/moe/sharded_moe.py`` (``top1gating:177``,
``top2gating:278`` — gumbel noise, capacity, load-balancing aux loss;
``_AllToAll:89``; ``MOELayer:439``: gate → dispatch all-to-all → experts →
combine all-to-all).

TPU design: dispatch/combine are einsums with a dispatch mask; sharding
constraints place tokens over the batch axes and experts over the ``ep``
axis, and the XLA partitioner materialises the two all-to-alls the reference
issues explicitly.  Capacity is static (computed from shapes at trace time)
so the program never retraces.  Everything is fp32 at the gate (reference
casts gate logits to fp32 too).
"""

import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import (DP_AXIS, EP_AXIS, FSDP_AXIS,
                                             TP_AXIS)
from deepspeed_tpu.runtime.zero.stage_plan import maybe_constrain

TOKENS_SPEC = P((DP_AXIS, FSDP_AXIS, EP_AXIS), None)        # [tokens, d]
DISPATCH_SPEC = P(EP_AXIS, None, None)                      # [e, c, d]


class GateOutput(NamedTuple):
    l_aux: jnp.ndarray            # load-balancing loss (scalar)
    combine_weights: jnp.ndarray  # [tokens, E, C] fp32 (None in compact mode)
    dispatch_mask: jnp.ndarray    # [tokens, E, C] bool (None in compact mode)
    exp_counts: jnp.ndarray       # [E] tokens routed per expert (pre-capacity)
    # compact routing (scatter dispatch): flat slot e*C + c per assignment,
    # E*C for dropped; gate weight per assignment
    slots: jnp.ndarray = None       # [tokens, k] int32
    gate_vals: jnp.ndarray = None   # [tokens, k] fp32
    capacity: int = 0


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def top1gating(logits, capacity_factor=1.0, min_capacity=4,
               noisy_gate_policy: Optional[str] = None, rng=None,
               drop_tokens=True, used_token_mask=None,
               build_dense=True) -> GateOutput:
    """Top-1 gating (Switch). logits: [tokens, E] fp32.

    Mirrors reference ``top1gating``: optional jitter/RSample noise, position
    within expert via masked cumsum, tokens beyond capacity dropped, aux loss
    = E * mean(me·ce).  ``build_dense=False`` skips materializing the
    [tokens, E, C] combine/dispatch tensors and returns only the compact
    (slots, gate_vals) routing the scatter dispatch consumes.
    """
    tokens, E = logits.shape
    C = _capacity(tokens, E, capacity_factor, min_capacity)
    if not drop_tokens:
        C = tokens  # worst case: everything to one expert

    logits = logits.astype(jnp.float32)
    if noisy_gate_policy == "RSample" and rng is not None:
        noisy = logits + jax.random.gumbel(rng, logits.shape)
    elif noisy_gate_policy == "Jitter" and rng is not None:
        noisy = logits * jax.random.uniform(rng, logits.shape, minval=0.98,
                                            maxval=1.02)
    else:
        noisy = logits

    gates = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(noisy, axis=-1)                        # [tokens]
    mask1 = _one_hot(idx, E)                                # [tokens, E]
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None]

    exp_counts = jnp.sum(mask1, axis=0)
    # aux loss (reference l_aux = E * sum(me*ce))
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # position of each token within its expert queue
    pos_in_expert = jnp.cumsum(mask1, axis=0) - mask1       # [tokens, E]
    pos = jnp.sum(pos_in_expert * mask1, axis=-1)           # [tokens]
    keep = (pos < C)[:, None] * mask1                        # drop overflow

    gate_val = jnp.sum(gates * keep, axis=-1)               # [tokens]
    kept = jnp.sum(keep, axis=-1) > 0                       # [tokens]
    slots = jnp.where(kept, idx.astype(jnp.int32) * C
                      + pos.astype(jnp.int32), E * C)[:, None]
    gate_vals = (gate_val * kept)[:, None]
    if not build_dense:
        return GateOutput(l_aux=l_aux, combine_weights=None,
                          dispatch_mask=None, exp_counts=exp_counts,
                          slots=slots, gate_vals=gate_vals, capacity=C)
    loc = _one_hot(pos.astype(jnp.int32), C)                # [tokens, C]
    combine = gate_val[:, None, None] * keep[:, :, None] * loc[:, None, :]
    dispatch = combine > 0
    return GateOutput(l_aux=l_aux, combine_weights=combine,
                      dispatch_mask=dispatch, exp_counts=exp_counts,
                      slots=slots, gate_vals=gate_vals, capacity=C)


def top2gating(logits, capacity_factor=1.0, min_capacity=4, rng=None,
               second_policy="Rsample", build_dense=True) -> GateOutput:
    """Top-2 gating (GShard).  Capacity doubles (2 slots per token)."""
    tokens, E = logits.shape
    C = _capacity(tokens, E, capacity_factor * 2.0, min_capacity)

    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)

    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, E)
    logits_no1 = jnp.where(mask1 > 0, -jnp.inf, logits)
    if rng is not None and second_policy.lower() == "rsample":
        logits_no1 = logits_no1 + jax.random.gumbel(rng, logits.shape)
    idx2 = jnp.argmax(logits_no1, axis=-1)
    mask2 = _one_hot(idx2, E)

    exp_counts = jnp.sum(mask1 + mask2, axis=0)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    pos1 = jnp.cumsum(mask1, axis=0) - mask1
    pos2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0)[None]
    p1 = jnp.sum(pos1 * mask1, axis=-1)
    p2 = jnp.sum(pos2 * mask2, axis=-1)
    keep1 = (p1 < C)[:, None] * mask1
    keep2 = (p2 < C)[:, None] * mask2

    g1 = jnp.sum(gates * keep1, axis=-1)
    g2 = jnp.sum(gates * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    kept1 = jnp.sum(keep1, axis=-1) > 0
    kept2 = jnp.sum(keep2, axis=-1) > 0
    s1 = jnp.where(kept1, idx1.astype(jnp.int32) * C
                   + p1.astype(jnp.int32), E * C)
    s2 = jnp.where(kept2, idx2.astype(jnp.int32) * C
                   + p2.astype(jnp.int32), E * C)
    slots = jnp.stack([s1, s2], axis=1)
    gate_vals = jnp.stack([g1 * kept1, g2 * kept2], axis=1)
    if not build_dense:
        return GateOutput(l_aux=l_aux, combine_weights=None,
                          dispatch_mask=None, exp_counts=exp_counts,
                          slots=slots, gate_vals=gate_vals, capacity=C)
    loc1 = _one_hot(p1.astype(jnp.int32), C)
    loc2 = _one_hot(p2.astype(jnp.int32), C)
    combine = (g1[:, None, None] * keep1[:, :, None] * loc1[:, None, :] +
               g2[:, None, None] * keep2[:, :, None] * loc2[:, None, :])
    dispatch = combine > 0
    return GateOutput(l_aux=l_aux, combine_weights=combine,
                      dispatch_mask=dispatch, exp_counts=exp_counts,
                      slots=slots, gate_vals=gate_vals, capacity=C)


def topkgating(logits, k: int, capacity_factor=1.0, min_capacity=4,
               norm_topk=True, build_dense=True, drop_tokens=True,
               noisy_gate_policy=None, rng=None) -> GateOutput:
    """General top-k gating (k statically unrolled; the reference stops
    at k=2, but the modern MoE zoo — Qwen2-MoE/DBRX/OLMoE — routes top-4
    to top-8).  Same machinery as :func:`top2gating`: per-rank masked
    argmax, slot priority = (choice rank, token order), capacity
    ``tokens/E * cf * k``; aux loss keeps the reference-0.8.3 rank-1/E
    convention for k<=2 and switches to upstream general-topk's full-mask
    ``E*E/k`` scaling for k>2 (see the in-body comment), and
    ``norm_topk`` renormalizes over SURVIVING assignments (post-drop,
    like top2gating / the reference; Mixtral / Qwen2-MoE
    ``norm_topk_prob``).  False keeps raw softmax mass.
    ``drop_tokens=False`` sets C=tokens (an expert can never queue more
    than one assignment per token).  ``noisy_gate_policy`` perturbs the
    SELECTION logits only (RSample gumbel / Jitter), like top1gating."""
    tokens, E = logits.shape
    C = _capacity(tokens, E, capacity_factor * float(k), min_capacity)
    if not drop_tokens:
        C = tokens

    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)

    if noisy_gate_policy == "RSample" and rng is not None:
        select = logits + jax.random.gumbel(rng, logits.shape)
    elif noisy_gate_policy == "Jitter" and rng is not None:
        select = logits * jax.random.uniform(rng, logits.shape,
                                             minval=0.98, maxval=1.02)
    else:
        select = logits

    masks, idxs = [], []
    masked = select
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        m = _one_hot(idx, E)
        idxs.append(idx)
        masks.append(m)
        masked = jnp.where(m > 0, -jnp.inf, masked)

    exp_counts = sum(jnp.sum(m, axis=0) for m in masks)
    me = jnp.mean(gates, axis=0)
    if k <= 2:
        # reference 0.8.3 convention (top1/top2gating): balance loss from
        # the rank-1 assignment, scale E
        ce = jnp.mean(masks[0], axis=0)
        l_aux = jnp.sum(me * ce) * E
    else:
        # upstream general-topk convention: FULL top-k mask, scale E*E/k
        # (torch.mean(me*ce)*E*E/k == sum(me*ce)*E/k) — so k>2 training
        # (Qwen2-MoE/DBRX-style) sees the same balance pressure as the
        # framework it mirrors
        ce = jnp.mean(sum(masks).astype(jnp.float32), axis=0)
        l_aux = jnp.sum(me * ce) * E / k

    prev_counts = jnp.zeros((E,), jnp.float32)
    keeps, locs, kept_flags = [], [], []
    for m in masks:
        pos_in_expert = jnp.cumsum(m, axis=0) - m + prev_counts[None]
        p = jnp.sum(pos_in_expert * m, axis=-1)
        keep = (p < C)[:, None] * m
        keeps.append(keep)
        locs.append(p)
        kept_flags.append(jnp.sum(keep, axis=-1) > 0)
        prev_counts = prev_counts + jnp.sum(m, axis=0)

    # gate mass from SURVIVING assignments; renormalize after the drop
    g_list = [jnp.sum(gates * keep, axis=-1) for keep in keeps]
    if norm_topk:
        denom = jnp.maximum(sum(g_list), 1e-9)
        g_list = [g / denom for g in g_list]

    slot_cols = [jnp.where(kept, idx.astype(jnp.int32) * C
                           + p.astype(jnp.int32), E * C)
                 for idx, p, kept in zip(idxs, locs, kept_flags)]
    gval_cols = [g * kept for g, kept in zip(g_list, kept_flags)]
    slots = jnp.stack(slot_cols, axis=1)
    gate_vals = jnp.stack(gval_cols, axis=1)
    if not build_dense:
        return GateOutput(l_aux=l_aux, combine_weights=None,
                          dispatch_mask=None, exp_counts=exp_counts,
                          slots=slots, gate_vals=gate_vals, capacity=C)
    combine = sum(
        g[:, None, None] * keep[:, :, None]
        * _one_hot(p.astype(jnp.int32), C)[:, None, :]
        for g, keep, p in zip(g_list, keeps, locs))
    dispatch = combine > 0
    return GateOutput(l_aux=l_aux, combine_weights=combine,
                      dispatch_mask=dispatch, exp_counts=exp_counts,
                      slots=slots, gate_vals=gate_vals, capacity=C)


class TopKGate:
    """Parity shim of reference ``TopKGate:351`` as a functional object."""

    def __init__(self, model_dim, num_experts, k=1, capacity_factor=1.0,
                 eval_capacity_factor=1.0, min_capacity=4,
                 noisy_gate_policy=None, drop_tokens=True,
                 norm_topk_prob=True):
        assert 1 <= k <= num_experts, (k, num_experts)
        self.norm_topk_prob = norm_topk_prob
        self.model_dim = model_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens

    def init(self, rng):
        scale = 1.0 / math.sqrt(self.model_dim)
        return {"wg": jax.random.normal(
            rng, (self.model_dim, self.num_experts), jnp.float32) * scale}

    def __call__(self, gate_params, x, train=True, rng=None,
                 build_dense=True) -> GateOutput:
        logits = x.astype(jnp.float32) @ gate_params["wg"]
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(logits, cf, self.min_capacity,
                              self.noisy_gate_policy if train else None,
                              rng=rng, drop_tokens=self.drop_tokens,
                              build_dense=build_dense)
        if self.k == 2 and self.norm_topk_prob:
            # second-expert sampling noise only during training (eval must
            # be deterministic, matching the top-1 path)
            return top2gating(logits, cf, self.min_capacity,
                              rng=rng if train else None,
                              build_dense=build_dense)
        # k > 2 (or k=2 without renormalization): Qwen2-MoE/DBRX-era
        # routing; selection noise only during training
        return topkgating(logits, self.k, cf, self.min_capacity,
                          norm_topk=self.norm_topk_prob,
                          build_dense=build_dense,
                          drop_tokens=self.drop_tokens,
                          noisy_gate_policy=(self.noisy_gate_policy
                                             if train else None),
                          rng=rng if train else None)


def moe_layer_forward(gate: TopKGate, gate_params, expert_params, expert_fn,
                      x, train=True, rng=None, dispatch_impl="scatter"):
    """The MOELayer hot path (reference ``MOELayer.forward:439``).

    x: [B, S, D] → tokens [B*S, D]; expert_params leaves have leading E dim
    sharded over ``ep``; returns (out [B,S,D], l_aux, exp_counts).

    The sharding constraints around dispatch/combine reproduce the
    reference's explicit all-to-alls: tokens are sharded over the batch
    axes, the dispatched tensor over ``ep`` — the transition is an
    all-to-all over ICI.

    ``dispatch_impl``:

    * ``"scatter"`` (default) — compact routing: each kept assignment
      scatter-adds its token into slot ``e*C + c`` of the [E·C, D] buffer
      and combine gathers back with the gate weight.  O(T·k·D) work; the
      dense [T, E, C] tensors are never built.
    * ``"einsum"`` — the GShard-style one-hot einsums (O(T·E·C·D) FLOPs,
      quadratic in tokens at fixed capacity factor).  Kept as the oracle:
      both paths produce identical outputs (same cumsum slot priority).
    """
    B, S, D = x.shape
    tokens = x.reshape(B * S, D)
    tokens = maybe_constrain(tokens, TOKENS_SPEC)

    out = gate(gate_params, tokens, train=train, rng=rng,
               build_dense=dispatch_impl == "einsum")
    if dispatch_impl == "einsum":
        # dispatch: [tokens, E, C] × [tokens, D] → [E, C, D] (all-to-all #1)
        dispatched = jnp.einsum("tec,td->ecd",
                                out.dispatch_mask.astype(x.dtype), tokens)
    else:
        C, E, k = out.capacity, out.exp_counts.shape[0], out.slots.shape[1]
        flat_slots = out.slots.reshape(-1)                 # [T*k]
        tokens_k = jnp.broadcast_to(
            tokens[:, None, :], (tokens.shape[0], k, D)).reshape(-1, D)
        # row E*C absorbs dropped assignments; distinct slots → no collide
        buf = jnp.zeros((E * C + 1, D), x.dtype)
        buf = buf.at[flat_slots].add(tokens_k)             # all-to-all #1
        dispatched = buf[:E * C].reshape(E, C, D)

    dispatched = maybe_constrain(dispatched, DISPATCH_SPEC)
    expert_out = expert_fn(expert_params, dispatched)      # [E, C, D]
    expert_out = maybe_constrain(expert_out, DISPATCH_SPEC)

    if dispatch_impl == "einsum":
        # combine: [tokens, E, C] × [E, C, D] → [tokens, D] (all-to-all #2)
        combined = jnp.einsum("tec,ecd->td",
                              out.combine_weights.astype(x.dtype),
                              expert_out)
    else:
        # replicate before the combine gather (this IS all-to-all #2's
        # traffic): XLA's partitioned gather over the unevenly sharded
        # [E*C+1, D] buffer reads wrong rows under ep sharding, silently
        # corrupting combined outputs vs the unsharded oracle.  The
        # gather also stays on the EVEN [E*C, D] buffer with dropped
        # assignments (slot == E*C) clipped and masked to zero — a
        # gather from a concat-padded [E*C+1, D] buffer miscompiles
        # under vmap (pipeline stages batch this layer): the partitioner
        # re-shards the uneven concat behind the replication constraint
        # and the batched gather again reads wrong rows
        eo = maybe_constrain(expert_out.reshape(E * C, D), P(None, None))
        safe = jnp.clip(out.slots, 0, E * C - 1)
        gathered = eo[safe] * \
            (out.slots < E * C)[..., None]                 # dropped read 0
        combined = jnp.sum(
            gathered * out.gate_vals[..., None].astype(x.dtype),
            axis=1)                                        # all-to-all #2
    combined = maybe_constrain(combined, TOKENS_SPEC)
    return combined.reshape(B, S, D), out.l_aux, out.exp_counts


# ----------------------------------------------------------------------
# dropless routing and a chip's share of the experts
# ----------------------------------------------------------------------
# Columns of the terms that the tokens gather back at a time
# (``dropless_held_experts``): a chunk's rows of so many columns fit the
# v5e's VMEM, where XLA then keeps them for the ``k`` gathers (out of HBM a
# gather of rows runs at a seventh of the chip's bandwidth: GLM-5's 6,144
# columns at once took 0.77 ms a gather of 8,192 rows, 0.05 in three blocks)
COMBINE_COLS = 2048


def dropless_route(h, wg, bias, k, scoring="sigmoid", scale=1.0, norm=True,
                   norm_eps=0.0, with_scores=False):
    """Routing with no capacity: every token gets its ``k`` experts.
    Scores over ALL experts in float32 — ``sigmoid(h wg)`` (``noaux_tc``
    with one group) or ``softmax(h wg)`` — the ``k`` largest of ``scores +
    bias`` chosen (``bias``: the selection bias, or None; ties to the
    lower index), weighted by their UNBIASED scores, normalised over the
    chosen ones if ``norm`` (their sum plus ``norm_eps``, where a model
    states one), times ``scale``.  h: [N, d] ->
    (chosen [N, k] int32, weights [N, k] float32), and the scores [N, E]
    with ``with_scores``.  Differentiable through the chosen scores (the
    choice itself has no gradient)."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_scoring {scoring!r}: 'sigmoid' or 'softmax'")
    logits = jnp.dot(h.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = scores if bias is None else scores + bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    chosen = chosen.astype(jnp.int32)
    if with_scores:
        return chosen, weights * scale, scores
    return chosen, weights * scale


def balance_statistic(chosen, scores):
    """``E * sum_e f_e P_e`` of one expert layer's routing (the Switch /
    GShard load-balancing term): ``f_e`` the share of the (token, choice)
    pairs on expert ``e``, ``P_e`` the mean router score of ``e``, both
    over ALL ``E`` experts of the router; 1.0 at a uniform routing.  The
    gradient flows through ``P`` alone."""
    E = scores.shape[-1]
    share = jnp.mean(
        (chosen[..., None] == jnp.arange(E)).astype(jnp.float32),
        axis=(0, 1))
    return E * jnp.sum(share * jnp.mean(scores, axis=0))


class _HeldPlan(NamedTuple):
    """Where one call's (token, expert) pairs lie in the sorted, tiled
    list (all int32 / bool: nothing here has a gradient)."""
    load: Any           # [held] pairs an expert
    begin: Any          # [held] an expert's first sorted pair
    start: Any          # [held] its first row tile
    n_tiles: Any        # scalar: tiles that hold pairs
    tile_expert: Any    # the expert of every tile the list can have
    order: Any          # [N k] sorted place -> pair
    row: Any            # [N, k] each pair's row in the padded list
    mine: Any           # [N, k] the pair's expert is held here
    layer: Any          # the layer to read in stacked leaves (or 0)


class _HeldStatic(NamedTuple):
    act: Any
    tiles: Any          # glu.ExpertTiles
    kernel: bool        # the Pallas kernels, else their jnp cousins
    interpret: bool
    stacked: bool       # the leaves are [L, held, ...]


def _chunk_rows(plan, tile, chunk_tiles, n_pairs, c):
    """Chunk ``c`` of the sorted list: (first tile, each row's sorted place
    [tiles, tile], which rows hold a pair, each pair's row in the chunk
    [N, k], which pairs are held here and lie in it)."""
    base = c * chunk_tiles
    t = jnp.minimum(base + jnp.arange(chunk_tiles),
                    plan.tile_expert.shape[0] - 1)
    e = plan.tile_expert[t]
    before = (t - plan.start[e]) * tile
    in_tile = jnp.arange(tile)[None, :]
    at = jnp.minimum((plan.begin[e] + before)[:, None] + in_tile, n_pairs - 1)
    local = plan.row - base * tile
    here = plan.mine & (local >= 0) & (local < chunk_tiles * tile)
    return (base, at, in_tile < (plan.load[e] - before)[:, None],
            jnp.clip(local, 0, chunk_tiles * tile - 1), here)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_sum(static, h, weights, w_gate, w_up, w_down, plan):
    """``sum_j w[n, j] GLU_{chosen[n, j]}(h[n])`` over the held pairs, by
    the plan: [N, d] float32."""
    from deepspeed_tpu.ops.pallas import grouped_expert_glu as glu
    N, d = h.shape
    k = plan.row.shape[1]
    held = plan.load.shape[0]
    tiles, tile = static.tiles, static.tiles.rows
    product = glu.grouped_expert_glu if static.kernel else glu.grouped_glu_jnp
    layer = plan.layer if static.stacked else None
    # a chunk: the rows that one pair a token fills, every expert padded
    chunk_tiles = -(-N // tile) + held
    token_sorted = plan.order // k

    def chunk(c, out):
        base, at, valid, local, here = _chunk_rows(plan, tile, chunk_tiles,
                                                   N * k, c)
        token = jnp.where(valid, token_sorted[at], 0)
        x = h[token.reshape(-1)]
        y = product(x, w_gate, w_up, w_down, plan.tile_expert,
                    jnp.minimum(plan.n_tiles - base, chunk_tiles),
                    static.act, tiles, layer=layer, base=base,
                    interpret=static.interpret)
        # each token's terms of this chunk, back at the token, a block of
        # columns at a time (``COMBINE_COLS``)
        blocks = []
        for b, block in enumerate(out):
            part = y[:, b * cols:(b + 1) * cols]
            for j in range(k):
                block = block + jnp.where(
                    here[:, j, None],
                    part[local[:, j]].astype(jnp.float32)
                    * weights[:, j, None], 0.0)
            blocks.append(block)
        return tuple(blocks)

    cols = COMBINE_COLS if d % COMBINE_COLS == 0 else d
    out = jax.lax.fori_loop(
        0, -(-plan.n_tiles // chunk_tiles), chunk,
        tuple(jnp.zeros((N, cols), jnp.float32) for _ in range(d // cols)))
    return jnp.concatenate(out, axis=1)


def _held_sum_fwd(static, h, weights, w_gate, w_up, w_down, plan):
    # nothing of the forward is kept but its operands: the backward runs
    # the gate and up products again, a chunk at a time
    return (_held_sum(static, h, weights, w_gate, w_up, w_down, plan),
            (h, weights, w_gate, w_up, w_down, plan))


def _held_sum_bwd(static, kept, d_out):
    """The same chunks again.  A chunk gathers its rows of ``h`` and of the
    cotangent (the weighted combine's transpose is a gather by the sorted
    order; a tile's padding rows get zeros and so add nothing to any
    gradient), the two kernels give the rows' gradient and add the tiles'
    weight gradients into their experts' float32 accumulators, and each
    token gathers its ``k`` rows' gradients back and sums them (the row
    gather's transpose; no scatter)."""
    from deepspeed_tpu.ops.pallas import grouped_expert_glu as glu
    h, weights, w_gate, w_up, w_down, plan = kept
    N, d = h.shape
    k = plan.row.shape[1]
    held, _, f = w_up.shape[-3:]
    tile = static.tiles.rows
    cols_dx, cols_dw = glu.backward_cols(tile, d, f, h.dtype.itemsize)
    dx_of, dw_of = (
        (glu.grouped_expert_glu_dx, glu.grouped_expert_glu_dw)
        if static.kernel else (glu.grouped_glu_dx_jnp, glu.grouped_glu_dw_jnp))
    layer = plan.layer if static.stacked else None
    chunk_tiles = -(-N // tile) + held
    g_out = d_out.astype(h.dtype)
    pair_weight = weights.astype(jnp.float32).reshape(-1)

    def chunk(c, carry):
        d_h, d_weights, acc = carry
        base, at, valid, local, here = _chunk_rows(plan, tile, chunk_tiles,
                                                   N * k, c)
        live = jnp.minimum(plan.n_tiles - base, chunk_tiles)
        pair = plan.order[at]
        token = jnp.where(valid, pair // k, 0).reshape(-1)
        valid = valid.reshape(-1)
        x = h[token]
        g = jnp.where(valid[:, None], g_out[token], 0)
        w = jnp.where(valid, pair_weight[pair.reshape(-1)], 0.0)
        dx, d_gate, d_up, inner, dw = dx_of(
            x, g, w, w_gate, w_up, w_down, plan.tile_expert, live,
            static.act, tile, cols_dx, layer=layer, base=base,
            interpret=static.interpret)
        acc = dw_of(x, g, d_gate, d_up, inner, acc, plan.tile_expert, live,
                    tile, cols_dw, base=base, interpret=static.interpret)
        for j in range(k):
            d_h = d_h + jnp.where(here[:, j, None],
                                  dx[local[:, j]].astype(jnp.float32), 0.0)
        return d_h, d_weights + jnp.where(here, dw[local], 0.0), acc

    d_h, d_weights, acc = jax.lax.fori_loop(
        0, -(-plan.n_tiles // chunk_tiles), chunk,
        (jnp.zeros((N, d), jnp.float32), jnp.zeros((N, k), jnp.float32),
         (jnp.zeros((held, d, f), jnp.float32),
          jnp.zeros((held, d, f), jnp.float32),
          jnp.zeros((held, f, d), jnp.float32))))

    def of_leaf(grad, leaf):
        grad = grad.astype(leaf.dtype)
        if not static.stacked:
            return grad
        # the one layer read in the stack; the others' gradients are zero
        return jax.lax.dynamic_update_index_in_dim(
            jnp.zeros_like(leaf), grad, plan.layer, 0)

    return (d_h.astype(h.dtype), d_weights.astype(weights.dtype),
            of_leaf(acc[0], w_gate), of_leaf(acc[1], w_up),
            of_leaf(acc[2], w_down), None)


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def dropless_held_experts(h, chosen, weights, experts, act, first=0,
                          tile=None, layer=None, impl=None, interpret=False):
    """The held experts' part of a dropless expert layer: ``sum_e w_e
    GLU_e(h)`` over the (token, expert) pairs whose expert this chip
    holds — experts ``first .. first + E_held - 1`` of the ids in
    ``chosen``, along the leading axis of ``experts["w_gate" | "w_up" |
    "w_down"]``; pairs of the other experts are other chips' terms and
    are left out.  h: [N, d]; returns (out [N, d] float32, load [E_held]
    int32 pairs per held expert, the rows computed, tile padding
    included).

    ONE grouped product with no capacity.  The pairs are sorted by expert
    and each expert's group is padded to whole row tiles, so a tile has
    one expert; the kernel (``ops/pallas/grouped_expert_glu.py``) runs the
    tiles that hold pairs and finds each tile's weights in the leaves by
    its expert's index: an expert nobody chose reads no weights, nothing
    is sliced out of a leaf, no token is ever dropped.  The sorted list is
    at worst ``N * k`` rows and is never laid out: it runs in chunks of
    the rows one pair a token would fill (with every expert's padding), as
    many as hold pairs, one in the common case.  A chunk gathers its rows
    of ``h``, the kernel writes each pair's term at its sorted place, and
    each token gathers its ``k`` terms back and sums them with its weights
    in float32 (a pair outside the chunk, or of an expert held elsewhere,
    adds nothing; ``COMBINE_COLS`` columns at a time): no scatter anywhere (docs/serving.md, "The dropless
    expert layer").

    Differentiable (a ``custom_vjp``, :func:`_held_sum_bwd`) with respect
    to ``h``, ``weights`` (and so the router) and the three expert leaves:
    the backward is grouped products over the same tiles, in the same
    chunks, and follows the pairs as the forward does.

    ``tile``: the row tile (default: from the shapes,
    ``pick_expert_tiles``).  With ``layer`` (may be traced) the expert
    leaves are a STACK of layers' experts, [L, E_held, ...], and that
    layer's are read in place.  ``impl`` / ``interpret``: as
    ``ops/decode_attention.py:use_pallas`` reads them (None: the kernel on
    a TPU, the same product in jnp elsewhere)."""
    from deepspeed_tpu.ops.decode_attention import use_pallas
    from deepspeed_tpu.ops.pallas import grouped_expert_glu as glu
    N, d = h.shape
    k = chosen.shape[1]
    held, _, f = experts["w_up"].shape[-3:]
    tiles = glu.pick_expert_tiles(N, held, d, f, h.dtype.itemsize, rows=tile)
    tile = tiles.rows

    flat = chosen.reshape(-1) - first
    mine = (flat >= 0) & (flat < held)
    expert = jnp.where(mine, flat, held)
    load = jnp.sum(expert[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    begin = jnp.cumsum(load) - load         # an expert's first sorted pair
    group = -(-load // tile)                # its row tiles
    end = jnp.cumsum(group)                 # ... where they end
    start = end - group                     # ... and begin
    n_tiles = end[-1]
    # the expert of every tile the padded list can have
    tile_expert = jnp.minimum(held - 1, jnp.sum(
        jnp.arange(-(-N * k // tile) + held)[:, None] >= end[None, :],
        axis=1, dtype=jnp.int32))
    # sorted place -> pair, and each pair's row in the padded list
    pairs = jnp.arange(N * k, dtype=jnp.int32)
    expert_sorted, order = jax.lax.sort((expert, pairs), num_keys=1)
    at = jnp.minimum(expert_sorted, held - 1)
    row_sorted = start[at] * tile + pairs - begin[at]
    _, row = jax.lax.sort((order, row_sorted), num_keys=1)
    stacked = experts["w_up"].ndim == 4
    plan = _HeldPlan(load, begin, start, n_tiles, tile_expert, order,
                     row.reshape(N, k), mine.reshape(N, k),
                     jnp.asarray(layer if stacked else 0, jnp.int32))
    out = _held_sum(
        _HeldStatic(act, tiles, bool(use_pallas(impl)), bool(interpret),
                    stacked),
        h, weights.astype(jnp.float32), experts["w_gate"], experts["w_up"],
        experts["w_down"], plan)
    return out, load, n_tiles * tile
