"""Host spans of the serving engine, on the profiler's clock.

``span("decode", step=41, live=8)`` enters
``jax.profiler.TraceAnnotation("serve/decode", step=41, live=8)``: while a
``jax.profiler`` session runs, the span lands in the same ``.xplane.pb``
and on the same clock as the device's events, which is what lets an idle
gap of the chip be put down to a phase of ``ServingEngine.step()``. With
no session the annotation checks one flag and returns, so "tracing off"
is "no profiler session": there is no recorder, switch or sink here, and
this module keeps no state. The keyword counts come back as the event's
stats (``jax.profiler.ProfileData``: ``dict(event.stats)``).

A ``TraceAnnotation`` takes its counts when it is entered. A count that
is known only when the work ends goes on ``done``: a zero-length
``serve/<name>.done`` span entered as the last thing inside its parent.

docs/observability.md "Tracing a serving replica" names every span.
"""

PREFIX = "serve/"


def span(name: str, **counts):
    """Context manager: the host span ``serve/<name>`` carrying
    ``counts`` (numbers or short strings)."""
    # imported here: fms_fsdp_tpu.obs is also imported by processes that
    # must not load jax (supervisors, the smoke's parent)
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(PREFIX + name, **counts)


def done(name: str, **counts) -> None:
    """The counts of span ``name`` that were known only at its end, as a
    zero-length ``serve/<name>.done`` span."""
    with span(name + ".done", **counts):
        pass
