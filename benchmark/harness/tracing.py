"""Start and stop the JAX profiler round a window, and hand back the
trace in the neutral form of ``trace_reduce``. The trace is written
under ``<checkout>/.bench_trace`` (listed in .gitignore) and deleted
once it has been read: a run keeps nothing on disk but the compile
cache."""

import os
import shutil

from benchmark.harness import trace_reduce


class Tracer:
    def __init__(self, root, workload, keep=False):
        self.dir = os.path.join(root, ".bench_trace", workload)
        self.keep = keep
        self._window = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self):
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def load(self):
        path = trace_reduce.find_xplane(self.dir)
        trace = trace_reduce.load_xplane(path)
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def annotate(name):
    """A host span in the device trace, for the benchmark's own calls
    into the program (``bench.submit``, ``bench.step``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)
