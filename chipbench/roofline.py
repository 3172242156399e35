"""The least a kernel could take: operations and bytes the algorithm needs,
from shapes, over the chip's published peaks.  A roofline share is this
time over the kernel's time in the device trace; the larger of the two
terms names the bound.  Kept with the benchmark so that no PR that claims
a gain can change it."""


def bound_seconds(flops, nbytes, peaks):
    """(seconds, "compute" | "memory")."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def flash_attention_calls(batch, heads, seq, head_dim, itemsize=2):
    """Causal flash attention, one call of each kernel on [batch, seq,
    heads, head_dim]: (flops, bytes) of the forward, dq and dk/dv kernels.
    One causal matrix product is 2*B*H*S*S*D/2 operations; the forward has
    two (QK^T, PV), dq three (QK^T, dO V^T, dS K) and dk/dv four (QK^T,
    dO V^T, P^T dO, dS^T Q), because each backward kernel recomputes the
    scores.  Bytes are each operand read and each result written once
    (dk and dv leave in float32)."""
    product = 2 * batch * heads * seq * seq * head_dim / 2
    tensor = batch * heads * seq * head_dim * itemsize
    return {"fwd": (2 * product, 4 * tensor),
            "dq": (3 * product, 5 * tensor),
            "dkv": (4 * product, 4 * tensor + 2 * tensor * 4 // itemsize)}


def flash_attention_train_seconds(model, calls_per_layer, steps, peaks):
    """Least seconds of all flash kernels of ``steps`` optimizer steps on
    one chip.  ``calls_per_layer`` (kernel executions per layer and
    micro-batch, counted in the trace) is 3 without recomputation and 4
    when the forward runs again in the backward pass."""
    cost = flash_attention_calls(model["micro_batch"], model["heads"],
                                 model["seq"], model["head_dim"])
    forwards = calls_per_layer - 2
    per_layer = (forwards * bound_seconds(*cost["fwd"], peaks)[0]
                 + bound_seconds(*cost["dq"], peaks)[0]
                 + bound_seconds(*cost["dkv"], peaks)[0])
    return steps * model["gas"] * model["n_layers"] * per_layer


def ragged_paged_dispatch(new_tokens, contexts, model):
    """(flops, bytes) one call of the ragged paged-attention kernel needs
    in ONE layer: every sequence attends its ``context`` (tokens in the
    cache including its ``new_tokens`` queries), causally.  Bytes are the
    key and value pages that hold the context, read once, plus queries in
    and results out."""
    heads, kv_heads = model["heads"], model["kv_heads"]
    head_dim, page = model["head_dim"], model["page_size"]
    item = model["kv_bytes"]
    flops = nbytes = 0
    for context in contexts:
        new = min(new_tokens, context)
        pairs = new * (context - new) + new * (new + 1) / 2
        flops += 4 * pairs * heads * head_dim
        pages = -(-int(context) // page)
        nbytes += 2 * pages * page * kv_heads * head_dim * item
        nbytes += 2 * new * heads * head_dim * item
    return flops, nbytes


def ragged_paged_serve_seconds(model, dispatches, peaks):
    """Least seconds of the ragged kernel over ``dispatches`` (of the
    engine's step reports, ``engine.last_step``), all layers."""
    total = 0.0
    for d in dispatches:
        if d["phase"] == "prefill":
            if "real" not in d:
                continue
            cost = ragged_paged_dispatch(d["real"], [d["context"]], model)
        else:
            cost = ragged_paged_dispatch(1, d["contexts"], model)
        total += bound_seconds(*cost, peaks)[0]
    return total * model["n_layers"]
