"""Accelerator selection singleton.

Parity: reference ``accelerator/real_accelerator.py:39,57``
(``get_accelerator``/``set_accelerator``).  Selection honours the
``DSTPU_ACCELERATOR`` env var ("tpu" | "cpu"); default follows
``jax.default_backend()`` — the CPU (XLA-on-host) accelerator is the same
class pointed at CPU devices, since JAX abstracts both.  A backend that
fails to initialise raises; it is never read as "cpu".
"""

import os

ds_accelerator = None


def _validate_accelerator(accel_obj):
    from .abstract_accelerator import DeepSpeedAccelerator
    assert isinstance(accel_obj, DeepSpeedAccelerator), \
        f"{accel_obj.__class__.__name__} is not a DeepSpeedAccelerator"
    return accel_obj


def get_accelerator():
    global ds_accelerator
    if ds_accelerator is not None:
        return ds_accelerator

    accelerator_name = os.environ.get("DSTPU_ACCELERATOR", None)
    if accelerator_name is None:
        import jax
        accelerator_name = "cpu" if jax.default_backend() == "cpu" else "tpu"

    if accelerator_name == "cpu":
        from .cpu_accelerator import CPU_Accelerator
        ds_accelerator = CPU_Accelerator()
    else:
        from .tpu_accelerator import TPU_Accelerator
        ds_accelerator = TPU_Accelerator()
    return _validate_accelerator(ds_accelerator)


def set_accelerator(accel_obj):
    global ds_accelerator
    ds_accelerator = _validate_accelerator(accel_obj)
    return ds_accelerator
