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

    def close_window(self):
        """End ``bench.window``, where every reader cuts the trace
        (``trace_reduce.window_of``). Microseconds: the serving loop calls
        it between two engine steps, with requests still running."""
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self):
        """Close the window if it is still open and stop the profiler.
        That takes seconds to minutes (it collects the trace of every
        device event since ``start``): a loop that serves requests calls
        it only once it has returned, or every request then running gets
        one gap of that length."""
        import jax
        self.close_window()
        xspace = stop_session()
        if xspace is None:
            jax.profiler.stop_trace()
            return
        out = os.path.join(self.dir, "plugins", "profile", "run")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "host.xplane.pb"), "wb") as f:
            f.write(xspace)

    def load(self):
        path = trace_reduce.find_xplane(self.dir)
        trace = trace_reduce.load_xplane(path)
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def stop_session():
    """Stop JAX's profiler session and hand back the trace as the bytes
    of an ``.xplane.pb``, or None where this JAX keeps its session
    somewhere else (``stop`` then falls back to ``stop_trace``).
    ``jax.profiler.stop_trace()`` itself also converts the whole trace
    into a ``trace.json.gz`` that nothing here reads, which is most of
    the time it takes (PERF.md section 6, PR 31)."""
    from jax._src import profiler as impl
    state = getattr(impl, "_profile_state", None)
    session = getattr(state, "profile_session", None)
    if session is None or not hasattr(session, "stop"):
        return None
    with state.lock:
        xspace = session.stop()
        state.reset()
    return xspace


def annotate(name):
    """A host span in the device trace, for the benchmark's own calls
    into the program (``bench.submit``, ``bench.step``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)
