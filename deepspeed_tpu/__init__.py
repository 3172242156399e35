"""deepspeed_tpu — a TPU-native training/inference framework with the
capabilities of DeepSpeed (reference: FreyaRao/DeepSpeed 0.8.3), built on
JAX/XLA/Pallas.

Top-level API parity: reference ``deepspeed/__init__.py`` (``initialize:52``,
``init_inference:233``, ``init_distributed``, ``add_config_arguments``).
"""

__version__ = "0.1.0"
__git_hash__ = None
__git_branch__ = None

import time as _time

_import_began_ns = _time.perf_counter_ns()     # setup/import starts here

from deepspeed_tpu.monitor.telemetry import setup_span as _setup_span  # noqa: E402

# everything the package pulls in, JAX included when this is the first
# import of it, is one ``setup/import`` span of the compile account
with _setup_span("setup/import", since_ns=_import_began_ns):
    from deepspeed_tpu.accelerator import get_accelerator, set_accelerator  # noqa: F401
    from deepspeed_tpu import comm  # noqa: F401
    from deepspeed_tpu.comm.comm import init_distributed  # noqa: F401
    from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError  # noqa: F401
    from deepspeed_tpu.runtime import zero  # noqa: F401
    from deepspeed_tpu.utils.init_on_device import OnDevice  # noqa: F401
    from deepspeed_tpu.utils.logging import logger, log_dist  # noqa: F401
    from deepspeed_tpu import module_inject, ops  # noqa: F401
    from deepspeed_tpu.runtime import DeepSpeedOptimizer, ZeROOptimizer  # noqa: F401
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine  # noqa: F401
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine  # noqa: F401
    from deepspeed_tpu.inference.engine import InferenceEngine  # noqa: F401
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig  # noqa: F401
    from deepspeed_tpu.runtime.lr_schedules import add_tuning_arguments  # noqa: F401
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing  # noqa: F401
    from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerLayer,  # noqa: F401
                                               DeepSpeedTransformerConfig)
    from deepspeed_tpu.module_inject import (replace_transformer_layer,  # noqa: F401
                                             revert_transformer_layer)


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               tp_rules=None,
               collate_fn=None,
               config=None,
               config_params=None):
    """Initialise the training engine.

    Parity: reference ``deepspeed/__init__.py:52``.  Differences forced by the
    functional paradigm:

    * ``model`` is a callable ``loss_fn(params, batch, rng) -> loss`` (or an
      object with ``.loss``), not an ``nn.Module``;
    * ``model_parameters`` is the params *pytree* (it is required);
    * ``optimizer`` (optional) is an optax ``GradientTransformation``;
    * ``mesh``/``tp_rules`` configure the device mesh and tensor-parallel
      sharding rules (the reference takes an ``mpu`` object for this).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    assert model is not None, "deepspeed_tpu.initialize: model is required"
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config") \
            and args.deepspeed_config is not None:
        config = args.deepspeed_config
    assert config is not None, \
        "DeepSpeed requires --deepspeed_config or the config= argument"

    if not isinstance(config, DeepSpeedConfig):
        config = DeepSpeedConfig(config)

    # PipelineModule models get the pipeline engine — parity:
    # reference deepspeed/__init__.py:124-148
    engine_cls = (PipelineEngine if isinstance(model, PipelineModule)
                  else DeepSpeedEngine)
    engine = engine_cls(
        model=model,
        config=config,
        params=model_parameters,
        optimizer=optimizer,
        lr_scheduler=lr_scheduler,
        mesh=mesh,
        tp_rules=tp_rules,
        collate_fn=collate_fn,
        training_data=training_data)

    return engine, engine.tx, engine.training_dataloader, engine.lr_scheduler


def create_serving_engine(model, params, config=None, overlay_path=None,
                          **kwargs):
    """Build a paged-KV :class:`~deepspeed_tpu.inference.serving
    .ServingEngine` from a ds-style config dict, applying a persisted
    autotuner overlay (``autotuning.overlay_path`` or the explicit
    ``overlay_path``) first — the serving twin of :func:`initialize`'s
    overlay hook."""
    from deepspeed_tpu.inference.serving import create_serving_engine as _f
    return _f(model, params, config=config, overlay_path=overlay_path,
              **kwargs)


def init_inference(model=None, config=None, params=None, mesh=None, **kwargs):
    """Parity: reference ``deepspeed/__init__.py:233``.  Config kwargs
    (``mp_size=2`` etc.) merge into ``config`` like the reference; ``params``
    is the weights pytree (functional-paradigm addition)."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine

    cfg_dict = dict(config or {})
    cfg_dict.update(kwargs)
    cfg = DeepSpeedInferenceConfig(cfg_dict)

    # HF torch model → policy-driven conversion (reference
    # replace_transformer_layer kernel injection path)
    from deepspeed_tpu.module_inject import is_hf_model, replace_transformer_layer
    if model is not None and is_hf_model(model):
        model, params = replace_transformer_layer(model)
    return InferenceEngine(model, cfg, params=params, mesh=mesh)


def add_config_arguments(parser):
    """Parity: reference ``deepspeed/__init__.py add_config_arguments``."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag, parity)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration")
    group.add_argument("--deepscale", default=False, action="store_true")
    group.add_argument("--deepscale_config", default=None, type=str)
    group.add_argument("--deepspeed_mpi", default=False, action="store_true")
    return parser
