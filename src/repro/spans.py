"""Host spans at the program's layer boundaries, on the profiler's clock.

`span(name, **args)` is a `jax.profiler.TraceAnnotation` named
"repro.<name>" with `args` as its keyword arguments: it costs about a
microsecond when no profile is running and lands, with its args, in the
same trace as the device's ops when one is. Take a `jax.profiler` trace
of the process to see them (docs/SERVING.md, "Tracing a server"); read
them back with `jax.profiler.ProfileData`, where an event's args come
back in `event.stats`. Spans nest by thread: a span's parent is the span
that encloses it on the same thread. Spans belong on the host only, never
inside jitted or Pallas code, where an annotation does nothing at run
time.
"""
import jax

PREFIX = "repro."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span "repro.<name>" carrying `args`; use it as a context
    manager (`set_metadata(**more)` adds args known only inside)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
