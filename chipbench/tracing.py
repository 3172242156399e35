"""The traced run's window: the JAX profiler, on for the first seconds of
the measured window, with the benchmark's own annotations
(``chipbench/step``, ``chipbench/submit``, ``chipbench/input``,
``chipbench/wait``) on the host's lines so that idle gaps of the device can
be named by what the host was doing."""

import shutil
import tempfile
import time

import jax

from chipbench import reduce

annotate = jax.profiler.TraceAnnotation


class Tracer:
    """``tick()`` at every loop boundary: starts the profiler at the first
    one and stops it at the first one ``seconds`` later, so whole steps
    are traced.  With ``on`` false it does nothing."""

    def __init__(self, on, seconds):
        self.on, self.seconds = bool(on), seconds
        self.active = False
        self._dir = self._started = None

    def tick(self):
        if not self.on:
            return
        if self._dir is None:
            self._dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # our annotations suffice
            jax.profiler.start_trace(self._dir, profiler_options=options)
            self._started = time.perf_counter()
            self.active = True
        elif self.active and \
                time.perf_counter() - self._started >= self.seconds:
            self.stop()

    def stop(self):
        if self.active:
            jax.profiler.stop_trace()
            self.active = False

    def trace(self):
        """The reduced trace, or None for an untraced run."""
        if self._dir is None:
            return None
        self.stop()
        try:
            return reduce.load_xplane(reduce.find_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
