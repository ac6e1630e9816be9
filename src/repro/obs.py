"""Spans of the serving path, on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``.  While a
profiler trace is being taken (``jax.profiler.trace(dir)``), it records a
host event ``name`` whose stats are ``meta``, on the same clock as the
device's programs, so an ``.xplane.pb`` alone says what the host was doing
while the device waited.  With no trace running it records nothing and
costs about a microsecond.  Metadata is ints only (step number, bucket,
rows, request uids); an event's parent is the span that encloses it, since
the serving path runs on one thread.

The names are fixed, since readers of the trace match them:

- ``repro.sched.step``: one scheduler quantum (``ContinuousBatcher.step``);
  ``repro.sched.admit``: one admission wave, from forming and padding it
  through its backend call, with the wave's uids (:func:`uids`);
  ``repro.sched.sample``: sampling and delivery of a call's tokens.
- ``repro.backend.prefill``, ``repro.backend.decode_step``,
  ``repro.backend.prefill_chunk``, ``repro.backend.verify_step``: one call
  into ``TensorBackend``; inside each, ``repro.backend.pager`` (block
  tables grown and pushed), ``repro.backend.dispatch`` (host arrays in,
  programs enqueued) and ``repro.backend.fetch`` (the blocking host copy of
  the logits).  ``repro.backend.tick``: one ``PipelineBackend`` tick.

JAX is imported on the first span, so modules that use this stay
importable without it.
"""
from __future__ import annotations

from typing import Dict, Iterable

_annotation = None
_uid_keys = ()


def span(name: str, **meta: int):
    """A context manager that records ``name`` while a trace is taken;
    ``.set_metadata(**meta)`` adds stats once they are known."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name, **meta)


def uids(values: Iterable[int]) -> Dict[str, int]:
    """``{"uid0": u0, "uid1": u1, ...}``: one int stat per request."""
    global _uid_keys
    values = list(values)
    if len(values) > len(_uid_keys):
        _uid_keys = tuple(f"uid{i}" for i in range(2 * len(values)))
    return dict(zip(_uid_keys, values))
