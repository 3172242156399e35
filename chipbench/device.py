"""The device as the benchmark sees it: which chips JAX found, the table of
peaks, compilations counted, peak memory.  A cell that needs a TPU and
finds none ends the process: there is no fallback."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(SystemExit):
    pass


def require_devices(chips, require_tpu=True):
    """The first ``chips`` devices, or exit non-zero with one line."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator("chipbench: no accelerator: "
                            + str(e).splitlines()[0])
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"chipbench: no accelerator: jax found platform "
                            f"{devices[0].platform!r}, need 'tpu'")
    if len(devices) < chips:
        raise NoAccelerator(f"chipbench: the cell needs {chips} chips, jax "
                            f"found {len(devices)}")
    return devices[:chips]


def peaks_for(device_kind):
    """Published peaks of one chip; an unknown device is an error, never a
    default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"chipbench/peaks.json has no device_kind "
                       f"{device_kind!r}; add it with its source")
    return table[device_kind]


def device_report(devices):
    """The ``device`` object of the result line, as JAX reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(max(peaks))}


class CompileCounter:
    """Compilations (and persistent-cache reads) through
    ``jax.monitoring``: each is one backend-compile event.  The listeners
    stay for the life of the process."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(**fields):
    """One JSON line on standard output, before the result line."""
    print(json.dumps(fields), flush=True)
