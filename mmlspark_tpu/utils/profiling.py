"""Profiling: a ``jax.profiler`` capture and the reference-style stopwatch.

The reference has no tracer — only ad-hoc ``StopWatch``/``Timer`` timings
(SURVEY.md §5). Here the XLA profiler is the tracer: :func:`trace` captures
a TensorBoard/Perfetto-loadable trace (``GET /debug/profile`` runs one on a
live server), and every layer boundary of the hot paths is already on its
timeline through the one span primitive,
:func:`mmlspark_tpu.observability.tracing.span` — ``PipelineStage``
fit/transform, ``BatchRunner``'s stages, the decoder's tick and the engine
loop (vocabulary: docs/observability.md).
"""

from __future__ import annotations

import contextlib

__all__ = ["trace", "StopWatch"]

from .shared import StopWatch  # re-export: the reference-style wall timer


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler trace into ``log_dir`` (TensorBoard format)."""
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
