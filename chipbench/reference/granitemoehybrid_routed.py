"""Plain float32 reference of the ROUTED Granite 4.0-H models
(``model_type: granitemoehybrid`` with ``num_local_experts`` > 0:
granite-4.0-h-small, 72 experts, 10 a token), written from the public
description of ``modeling_granitemoehybrid.py`` as the machine's notes hold
it (no network here: the source could not be re-read; what could not be
checked against the file itself is under ``assumed`` in the configuration's
file).  Nothing here imports the program; the weights arrive as its
parameter tree (names are the interface): ``params["periods"][j]`` the
layers of position ``j`` in the period of ``layer_types``, stacked on a
leading axis, each with its mixer's leaves (``reference/granitemoehybrid.py``
says which) and ``moe``: ``wg`` [L, d, E] the router over ALL published
experts, ``w_gate`` / ``w_up`` [L, held, d, f] and ``w_down`` [L, held, f,
d] the held experts (the two halves of the published ``input_linear``, the
activated half first, and ``output_linear``), ``shared`` one SwiGLU.

The stream, RMSNorm (eps ``rms_norm_eps``) everywhere, no bias but the
convolution's:
  x0 = E[ids] * embedding_multiplier
  x = x + residual_multiplier * mixer(norm(x; attn_norm))
  h = norm(x; mlp_norm);  x = x + residual_multiplier * (moe(h) + shared(h))
  logits = norm(x; final_norm) E^T / logits_scaling   (tied table)
  moe(h): r = h W_r, E logits in float32; chosen = the
  ``num_experts_per_tok`` largest (ties to the lower index); g = softmax
  over the CHOSEN logits; sum_e g_e (silu(h A_e) * (h B_e)) C_e, experts
  of width ``intermediate_size``
  shared(h) = (silu(h A_s) * (h B_s)) C_s, width ``shared_intermediate_size``
The mixers are ``reference/granitemoehybrid.py``'s, called from here:
``attention`` layers grouped-query, causal, NO positional embedding, scores
x ``attention_multiplier``; ``mamba`` layers the Mamba-2 recurrence token by
token.

THE CHIP'S SHARE: the expert leaves hold experts 0 .. num_local_experts-1
of the ``published`` count.  The router keeps its published width and its
experts a token, g is the softmax over ALL the chosen ones, a pair whose
expert is not held adds nothing here, the shared SwiGLU and the mixers are
whole, and that partial sum goes on to the next layer: here as in the
program.  No term stands in for the other chip.

``decided``: a row is undecided where, at some layer, its own token's
margin in r (this file's float32 logits) between a chosen and an unchosen
expert, at least one of them held here, is under ``MARGIN``.  The
program's router multiplies in float32 too, but its h has come through
bf16 weights and a bf16 stream: r drifts by about 2**-8 of the stream a
layer, and where the last chosen and the first unchosen logit lie closer
than that the two sides pick different experts and the row moves by
g_e x an expert's output with neither wrong.  One expert layer of 72 has
about 16 logits a unit at the tenth-largest, so a margin m leaves a token
near an event that concerns a held expert with probability about 12 m a
layer, 120 m over this cut's ten: 0.3 at 0.003, reckoned before any run.
Read on the chip (PERF.md section 4; ``scripts/expert_control.py --lose
none`` prints every row's margin beside its error): 12-24 of a run's 72
rows fall under 0.003 (17-33 %), the decided rows read 0.010-0.023 of the
check's scale and the undecided ones 0.007-0.021, every row of every run
under the tolerance: a flip at the boundary swaps the weakest of ten
weights (g about 0.04) and costs under 0.006 here.  So 0.003 is bf16's
drift of a logit (about 0.002 a layer's input) with half as much again,
not a fit to failing rows; it leaves two thirds to five sixths of the rows
compared, and it stays because nothing says the next seed's flip is as
cheap as these.

Memory: the comparison runs beside an engine that holds 12.5 GB, so one
layer's mixer, ONE expert at a time and the shared SwiGLU are cast to
float32 only while they run, never a stacked ``[held, d, f]`` leaf.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
from chipbench.reference import granitemoehybrid as dense

# the least gap in the reference's own router logits that counts as a
# decision (see above; PERF.md section 4)
MARGIN = 0.003
ROWS = 256              # an expert's rows are padded to multiples of this


def _f32(x):
    return x.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _mixer_step(sizes):
    """x + residual x mixer(norm(x)) and the feed-forward's normed input,
    for layer ``p`` (traced) of a stacked layer WITHOUT its expert leaves:
    cut out and cast to float32 only while it runs."""
    sizes = dict(sizes)

    @jax.jit
    def step(x, stacked, p):
        w = jax.tree_util.tree_map(lambda leaf: _f32(leaf[p]), stacked)
        h = common.rms_norm(x, w["attn_norm"], sizes["eps"])
        mixed = dense._mamba(h, w["ssm"], sizes) if "ssm" in w \
            else dense._attention(h, w, sizes)
        x = x + sizes["residual"] * mixed
        return x, common.rms_norm(x, w["mlp_norm"], sizes["eps"])

    return step


@functools.partial(jax.jit, static_argnames=("per_token", "held"))
def _route(h, wg, p, per_token, held):
    """(chosen [N, k], g [N, k], margin [N]) over ALL experts: ``margin``
    the least gap in the logits between a chosen and an unchosen expert of
    which at least one is held here (inf where no held expert is near
    enough to matter: none chosen and none first in line)."""
    r = h @ _f32(wg[p])
    top, chosen = jax.lax.top_k(r, per_token)
    g = jax.nn.softmax(top, axis=-1)
    is_chosen = jnp.zeros(r.shape, bool).at[
        jnp.arange(r.shape[0])[:, None], chosen].set(True)
    is_held = jnp.arange(r.shape[1]) < held
    best_out = jnp.max(jnp.where(is_chosen, -jnp.inf, r), -1, keepdims=True)
    # a held chosen expert over the best unchosen one, a held unchosen one
    # under the weakest chosen one
    gap = jnp.where(is_chosen, r - best_out, top[:, -1:] - r)
    return chosen, g, jnp.min(jnp.where(is_held, gap, jnp.inf), -1)


def _glu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


@jax.jit
def _expert_rows(out, h, rows, weights, back, experts, p, e):
    """Add expert ``e``'s term of layer ``p`` (both traced: one program a
    shape) for its ``rows`` of ``h``, weighted; ``back[t]`` is token
    ``t``'s row among them, or a padding row (weight 0).  A gather, not a
    scatter-add (``reference/afmoe.py`` says why)."""
    term = _glu(h[rows], experts["w_gate"][p, e], experts["w_up"][p, e],
                experts["w_down"][p, e]) * weights[:, None]
    return out + term[back]


@jax.jit
def _shared(h, shared, p):
    return _glu(h, shared["w_gate"][p], shared["w_up"][p],
                shared["w_down"][p])


def _expert_layer(h, moe, p, per_token, held):
    """The held experts' terms plus the shared SwiGLU, and each token's
    margin; h: [N, d]."""
    chosen, g, margin = _route(h, moe["wg"], p, per_token, held)
    chosen, g = np.asarray(chosen), np.asarray(g)
    out = jnp.zeros_like(h)
    experts = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    for e in range(held):       # ONE expert in float32 at a time
        tokens, slot = np.nonzero(chosen == e)
        pad = ROWS - len(tokens) % ROWS     # at least one padding row
        rows = np.concatenate([tokens, np.zeros(pad, tokens.dtype)])
        w = np.concatenate([g[tokens, slot], np.zeros(pad, g.dtype)])
        back = np.full(h.shape[0], len(tokens), np.int32)
        back[tokens] = np.arange(len(tokens), dtype=np.int32)
        out = _expert_rows(out, h, jnp.asarray(rows.astype(np.int32)),
                           jnp.asarray(w), jnp.asarray(back), experts, p, e)
    return out + _shared(h, moe["shared"], p), np.asarray(margin)


@jax.jit
def _head(x, norm, table, eps, scaling):
    return common.rms_norm(x, _f32(norm), eps) @ _f32(table).T / scaling


@common.highest
def logits_and_margins(params, ids, cfg, last=None):
    """ids: [B, S] -> (float32 logits [B, last, vocab], margin [B, last]):
    the rows of the ``last`` positions (all without it) and each row's
    least own-token routing margin over the layers."""
    assert cfg["num_local_experts"] > 0 and not params["layers"] and \
        cfg["position_embedding_type"] == "nope"
    sizes = dense._sizes(cfg)
    step = _mixer_step(tuple(sorted(sizes.items())))
    per_token, held = cfg["num_experts_per_tok"], cfg["num_local_experts"]
    B, S = ids.shape
    last = S if last is None else last
    x = _f32(params["tok_embed"][ids]) * float(cfg["embedding_multiplier"])
    margin = np.full((B * S,), np.inf, np.float32)
    periods = params["periods"]
    for p in range(jax.tree_util.tree_leaves(periods)[0].shape[0]):
        for stacked in periods:
            moe = stacked["moe"]
            x, h = step(x, {k: v for k, v in stacked.items() if k != "moe"},
                        p)
            out, near = _expert_layer(h.reshape(B * S, -1), moe, p,
                                      per_token, held)
            margin = np.minimum(margin, near)
            x = x + sizes["residual"] * out.reshape(x.shape)
    out = _head(x[:, S - last:], params["final_norm"], params["tok_embed"],
                cfg["rms_norm_eps"], float(cfg["logits_scaling"]))
    return out, margin.reshape(B, S)[:, S - last:]


def logits(params, ids, cfg, last=None):
    """ids: [B, S] -> (float32 logits [B, last, vocab], decided [B, last]):
    which rows this file's own routing leaves decided at ``MARGIN``."""
    out, margin = logits_and_margins(params, ids, cfg, last)
    return out, margin >= MARGIN
