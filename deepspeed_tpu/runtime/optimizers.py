"""Built-in optimizer registry.

Parity: reference ``runtime/engine.py:1321 _configure_basic_optimizer``
(Adam/AdamW → FusedAdam | DeepSpeedCPUAdam, Lamb, OneBit*, Adagrad).

TPU design: optimizers are optax ``GradientTransformation``s.  The reference's
"fused" multi-tensor CUDA kernels exist because eager torch launches one
kernel per tensor; under XLA every optimizer is already fused across the whole
pytree in one compiled program, so ``FusedAdam``/``Adam`` converge to the same
thing.  A standalone fused-Adam over a flat partition buffer exists in
``ops/adam.py`` (the op_builder surface; the engine's optax update compiles
to the same fused program).

``OneBitAdam``/``ZeroOneAdam``/``OneBitLamb`` (reference ``fp16/onebit/*``) are
error-feedback *communication* compressors; on TPU the gradient reduction is
inside XLA, so the analogue is sign-compressed gradient all-reduce implemented
in ``runtime/comm_compression.py`` and selected via the same optimizer names.
"""

from typing import Any, Callable, Dict

import optax

ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
FUSED_ADAM = "fusedadam"
CPU_ADAM = "cpuadam"  # host-offloaded Adam (ZeRO-Offload); see zero/offload
LAMB_OPTIMIZER = "lamb"
FUSED_LAMB = "fusedlamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ZERO_ONE_ADAM_OPTIMIZER = "zerooneadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
SGD_OPTIMIZER = "sgd"
ADAGRAD_OPTIMIZER = "adagrad"


def _fmix32(h):
    """murmur3's 32-bit finaliser on uint32 (array or scalar): a bijection
    in which every input bit flips each output bit with a probability close
    to one half.  Two multiplies and three xor-shifts an element."""
    import jax.numpy as jnp
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _rounding_seed(count, ordinal: int):
    """The uint32 scalar that sets one leaf's rounding noise for one step:
    mixed from the step ``count`` and the leaf's ``ordinal`` in the flattened
    tree, so that no two (step, leaf) pairs share a seed but by chance."""
    import jax.numpy as jnp
    step = _fmix32(count.astype(jnp.uint32) + jnp.uint32(0x9E3779B9))
    return _fmix32(step ^ jnp.uint32(ordinal * 0x85EBCA77 & 0xFFFFFFFF))


def _rounding_noise(seed, shape):
    """One uint32 word an element of a leaf of ``shape``: ``_fmix32`` of
    (the element's linear index in the GLOBAL leaf times an odd constant)
    xor ``seed``.  The product is built from ``broadcasted_iota`` over the
    leaf's own shape (no reshape) with the constant folded into each axis'
    stride, so XLA computes the word inside the fusion that consumes it (a
    small vector an axis and one add an element), a sharded leaf partitions
    without a gather, and an element's noise is a function of (seed,
    position) alone: the same on any mesh.  Without the constant the
    finaliser's high half is measurably uneven over a run of consecutive
    indices (a chi-square of its top byte over 2**20 of them reads 314
    where 255 is due); with it both halves read as uniform.  (The index
    wraps past 2**32 elements a leaf: the noise then repeats, still
    uniform.)"""
    import jax
    import jax.numpy as jnp
    spread = jnp.zeros(shape, jnp.uint32)
    stride = 0x9E3779B1            # odd: 2**32 over the golden ratio
    for axis in reversed(range(len(shape))):
        spread = spread + jax.lax.broadcasted_iota(jnp.uint32, shape, axis) \
            * jnp.uint32(stride & 0xFFFFFFFF)
        stride *= shape[axis]
    return _fmix32(spread ^ seed)


def _sr_cast(x32, noise, dtype):
    """Stochastic-round an fp32 array to ``dtype`` (bf16): add ``noise``
    (uint32, 16 uniform bits an element) to the truncated mantissa bits,
    then truncate.  Unbiased in expectation, so low-precision moment
    accumulation does not systematically lose the (1-beta)-scaled increments
    the way nearest-rounding does — the reason plain bf16 second moments
    decay under b2=0.999.  The noise is ``_rounding_noise``'s: a counter
    hash, no ``jax.random`` stream (two Threefry draws a leaf cost the
    update more than its bytes did; PERF.md §6, PR 55)."""
    import jax
    import jax.numpy as jnp
    if dtype == jnp.float32:
        return x32
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    out = (bits + noise) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(out, jnp.float32).astype(dtype)


def _scale_by_adam_dtyped(b1, b2, eps, moment_dtype) -> optax.GradientTransformation:
    """``optax.scale_by_adam`` with BOTH moments stored in ``moment_dtype``
    (optax only supports ``mu_dtype``).  Accumulation happens in fp32 every
    step; the stored state is stochastically rounded down to the target dtype.
    Halves Adam's optimizer-state HBM (8 bytes/param -> 4 at bf16), which is
    what lets a >=1B-param model train on one 16 GB chip without host offload
    (cf. reference ZeRO-Offload's motivation, runtime/zero/offload.py).

    The rounding's noise is one hashed word an element (``_rounding_noise``),
    its low half for ``mu`` and its high half for ``nu``, seeded from the
    step count and the leaf's place in the tree: reproducible from (step,
    leaf, position) on any mesh and after a resumed checkpoint."""
    import jax
    import jax.numpy as jnp

    def init(params):
        zeros = lambda p: jnp.zeros(jnp.shape(p), moment_dtype)  # noqa: E731
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params))

    def update(updates, state, params=None):
        del params
        count = state.count + 1
        cf = count.astype(jnp.float32)
        bc1 = 1.0 - jnp.power(jnp.float32(b1), cf)
        bc2 = 1.0 - jnp.power(jnp.float32(b2), cf)

        mu32 = jax.tree_util.tree_map(
            lambda g, m: b1 * m.astype(jnp.float32) +
            (1.0 - b1) * g.astype(jnp.float32), updates, state.mu)
        nu32 = jax.tree_util.tree_map(
            lambda g, v: b2 * v.astype(jnp.float32) +
            (1.0 - b2) * jnp.square(g.astype(jnp.float32)),
            updates, state.nu)
        out = jax.tree_util.tree_map(
            lambda m, v: (m / bc1) / (jnp.sqrt(v / bc2) + eps), mu32, nu32)

        leaves, treedef = jax.tree_util.tree_flatten(mu32)
        noise = treedef.unflatten([
            _rounding_noise(_rounding_seed(count, i), jnp.shape(leaf))
            for i, leaf in enumerate(leaves)])
        mu_new = jax.tree_util.tree_map(
            lambda m, r: _sr_cast(m, r & jnp.uint32(0xFFFF), moment_dtype),
            mu32, noise)
        nu_new = jax.tree_util.tree_map(
            lambda v, r: _sr_cast(v, r >> 16, moment_dtype), nu32, noise)
        return out, optax.ScaleByAdamState(count=count, mu=mu_new, nu=nu_new)

    return optax.GradientTransformation(init, update)


def _moment_dtype(params: Dict[str, Any]):
    import jax.numpy as jnp
    name = str(params.get("moment_dtype", "float32")).lower()
    table = {"float32": jnp.float32, "fp32": jnp.float32,
             "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16}
    if name not in table:
        raise ValueError(f"moment_dtype must be one of {sorted(table)}, "
                         f"got '{name}'")
    return table[name]


def _adam(params: Dict[str, Any], adamw_mode=True) -> optax.GradientTransformation:
    lr = params.get("lr", 1e-3)
    betas = params.get("betas", (0.9, 0.999))
    eps = params.get("eps", 1e-8)
    wd = params.get("weight_decay", 0.01 if adamw_mode else 0.0)
    mdt = _moment_dtype(params)
    import jax.numpy as jnp
    if mdt != jnp.float32:
        if params.get("_b1_schedule") is not None:
            raise ValueError("moment_dtype != float32 is not supported "
                             "together with OneCycle momentum cycling")
        # reduced-precision moments: custom scale_by_adam (optax only casts
        # mu), chained to match optax.adamw/adam semantics exactly
        tx = optax.chain(
            _scale_by_adam_dtyped(betas[0], betas[1], eps, mdt),
            optax.add_decayed_weights(wd) if (adamw_mode and wd)
            else optax.identity(),
            optax.scale_by_learning_rate(lr))
        if not adamw_mode and wd:
            tx = optax.chain(optax.add_decayed_weights(wd), tx)
        return tx
    b1_schedule = params.get("_b1_schedule")   # 1Cycle momentum cycling
    if b1_schedule is not None:
        # inject_hyperparams lets b1 follow a schedule (the reference's
        # OneCycle sets optimizer momentum per step); lr may itself be a
        # schedule — both are resolved per step
        base = optax.adamw if adamw_mode else optax.adam
        kw = dict(learning_rate=lr, b1=b1_schedule, b2=betas[1], eps=eps)
        if adamw_mode:
            kw["weight_decay"] = wd
        tx = optax.inject_hyperparams(base)(**kw)
        if not adamw_mode and wd:
            tx = optax.chain(optax.add_decayed_weights(wd), tx)
        return tx
    if adamw_mode:
        return optax.adamw(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)
    tx = optax.adam(lr, b1=betas[0], b2=betas[1], eps=eps)
    if wd:
        tx = optax.chain(optax.add_decayed_weights(wd), tx)
    return tx


def _lamb(params: Dict[str, Any]) -> optax.GradientTransformation:
    lr = params.get("lr", 1e-3)
    betas = params.get("betas", (0.9, 0.999))
    eps = params.get("eps", 1e-6)
    wd = params.get("weight_decay", 0.0)
    return optax.lamb(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=wd)


def _sgd(params: Dict[str, Any]) -> optax.GradientTransformation:
    lr = params.get("lr", 1e-3)
    momentum = params.get("momentum", 0.0)
    nesterov = params.get("nesterov", False)
    wd = params.get("weight_decay", 0.0)
    tx = optax.sgd(lr, momentum=momentum or None, nesterov=nesterov)
    if wd:
        tx = optax.chain(optax.add_decayed_weights(wd), tx)
    return tx


def _adagrad(params: Dict[str, Any]) -> optax.GradientTransformation:
    lr = params.get("lr", 1e-2)
    eps = params.get("eps", 1e-10)
    return optax.adagrad(lr, eps=eps)


def _onebit(params: Dict[str, Any],
            inner: optax.GradientTransformation
            ) -> optax.GradientTransformation:
    """Two-stage 1-bit optimizer (reference ``fp16/onebit/*``): warmup runs
    the inner rule on raw grads; after ``freeze_step`` the gradient is
    sign-quantized with error feedback (``runtime/comm_compression.py``)
    before the inner update — the trajectory of compressed communication."""
    from deepspeed_tpu.runtime.comm_compression import error_feedback_compress
    freeze_step = int(params.get("freeze_step", 100))
    return optax.chain(error_feedback_compress(freeze_step), inner)


def _onebit_adam(params: Dict[str, Any]) -> optax.GradientTransformation:
    return _onebit(params, _adam(params, adamw_mode=False))


def _onebit_lamb(params: Dict[str, Any]) -> optax.GradientTransformation:
    return _onebit(params, _lamb(params))


OPTIMIZER_REGISTRY: Dict[str, Callable[[Dict[str, Any]], optax.GradientTransformation]] = {
    ADAM_OPTIMIZER: lambda p: _adam(p, adamw_mode=p.get("adam_w_mode", True)),
    ADAMW_OPTIMIZER: lambda p: _adam(p, adamw_mode=True),
    FUSED_ADAM: lambda p: _adam(p, adamw_mode=p.get("adam_w_mode", True)),
    CPU_ADAM: lambda p: _adam(p, adamw_mode=p.get("adamw_mode", True)),
    LAMB_OPTIMIZER: _lamb,
    FUSED_LAMB: _lamb,
    ONEBIT_ADAM_OPTIMIZER: _onebit_adam,
    ZERO_ONE_ADAM_OPTIMIZER: _onebit_adam,
    ONEBIT_LAMB_OPTIMIZER: _onebit_lamb,
    SGD_OPTIMIZER: _sgd,
    ADAGRAD_OPTIMIZER: _adagrad,
}

# Optimizers whose comm path uses 1-bit sign compression with error feedback
COMPRESSED_COMM_OPTIMIZERS = {
    ONEBIT_ADAM_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER, ONEBIT_LAMB_OPTIMIZER,
}


def build_optimizer(name: str, params: Dict[str, Any]) -> optax.GradientTransformation:
    key = name.lower()
    if key not in OPTIMIZER_REGISTRY:
        raise ValueError(f"Unknown optimizer '{name}'. "
                         f"Built-ins: {sorted(OPTIMIZER_REGISTRY)}")
    return OPTIMIZER_REGISTRY[key](params)
