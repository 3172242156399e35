"""Op-builder registry.

Parity: reference ``op_builder/builder.py:112`` (``OpBuilder``/``CUDAOpBuilder``
— per-op subclass with NAME, compat probe, JIT/AOT compile) and
``op_builder/all_ops.py`` (reflection into ``ALL_OPS``).

TPU design: "building" a Pallas op is tracing+compiling it through XLA, so an
OpBuilder here is a *capability probe + loader*: ``is_compatible()`` checks
the backend supports the kernel (TPU generation, dtype support, or — for
native host ops — a compiled C extension), and ``load()`` returns the op
module.  Every Pallas op ships a jnp reference implementation used as the
fallback (and as the test oracle), selected automatically when Pallas is not
available (e.g. CPU CI).
"""

import importlib

from deepspeed_tpu.utils.logging import logger


class OpBuilder:
    BUILD_VAR = "DSTPU_BUILD_OPS"
    NAME = "op"
    MODULE = None  # python module path providing the op

    def __init__(self):
        self.error_log = None

    def is_compatible(self, verbose=True):
        try:
            self.load()
            return True
        except Exception as e:  # pragma: no cover
            self.error_log = str(e)
            if verbose:
                logger.warning(f"op {self.NAME} incompatible: {e}")
            return False

    def load(self, verbose=True):
        assert self.MODULE, f"{self.NAME} has no module"
        return importlib.import_module(self.MODULE)

    def builder(self):
        return self

    @staticmethod
    def pallas_supported():
        import jax
        return jax.default_backend() == "tpu"


class PallasOpBuilder(OpBuilder):
    """Ops with a Pallas fast path and a jnp fallback."""

    def jnp_fallback(self):
        mod = self.load()
        return getattr(mod, "reference_impl", None)


class FusedAdamBuilder(PallasOpBuilder):
    NAME = "fused_adam"
    MODULE = "deepspeed_tpu.ops.adam"


class FusedLambBuilder(PallasOpBuilder):
    NAME = "fused_lamb"
    MODULE = "deepspeed_tpu.ops.lamb"


class CPUAdamBuilder(OpBuilder):
    NAME = "cpu_adam"
    MODULE = "deepspeed_tpu.ops.cpu_adam"


class CPUAdagradBuilder(OpBuilder):
    NAME = "cpu_adagrad"
    MODULE = "deepspeed_tpu.ops.cpu_adam"


class TransformerBuilder(PallasOpBuilder):
    NAME = "transformer"
    MODULE = "deepspeed_tpu.ops.attention"


class InferenceBuilder(PallasOpBuilder):
    NAME = "transformer_inference"
    MODULE = "deepspeed_tpu.ops.decode_attention"


class QuantizerBuilder(PallasOpBuilder):
    NAME = "quantizer"
    MODULE = "deepspeed_tpu.ops.quantizer"


class SparseAttnBuilder(PallasOpBuilder):
    NAME = "sparse_attn"
    MODULE = "deepspeed_tpu.ops.attention"


class RandomLTDBuilder(OpBuilder):
    NAME = "random_ltd"
    MODULE = "deepspeed_tpu.ops.random_ltd"


class AsyncIOBuilder(OpBuilder):
    NAME = "async_io"
    MODULE = "deepspeed_tpu.ops.aio"


class UtilsBuilder(OpBuilder):
    NAME = "utils"
    MODULE = "deepspeed_tpu.ops.flatten"


ALL_OPS = {
    b.NAME: b for b in [
        FusedAdamBuilder(), FusedLambBuilder(), CPUAdamBuilder(),
        CPUAdagradBuilder(), TransformerBuilder(), InferenceBuilder(),
        QuantizerBuilder(), SparseAttnBuilder(), RandomLTDBuilder(),
        AsyncIOBuilder(), UtilsBuilder(),
    ]
}
