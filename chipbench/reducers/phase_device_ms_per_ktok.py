"""Milliseconds the device was busy inside the executions of one
``phase``'s dispatches (cut by launch order: ``launch_order``) per 1024 of
their ``tokens``, over the traced window: for ``prefill``, what a thousand
dispatched prompt positions cost the device, padding included, whether or
not the serve loop waited for the chunk."""

from chipbench.reducers import launch_order


def read(run, phase):
    found = launch_order.device_seconds(run, phase)
    if not found:
        return None
    seconds, ran = found
    tokens = sum(d["batch"] * d["tokens"] for d in ran)
    return seconds * 1e3 / (tokens / 1024.0) if tokens else None
