"""Named layers of the program: host spans and device scopes.

Two kinds of name, one list of each, shared by the program and by whatever
reads a profile of it:

- **Device scopes** (:data:`SCOPES`) name the layers of the jitted round.
  :func:`scope` wraps a function in ``jax.named_scope``, so every operation
  it stages — and, through autodiff, every operation of its backward pass,
  under ``transpose(jvp(<scope>))`` — carries the name in its HLO
  ``op_name`` metadata, and a device profile can charge each operation to
  its layer.  Scopes are metadata only: the arithmetic and the outputs of a
  scoped program are bit-identical to the unscoped one's.
- **Host spans** (:data:`SPAN_NAMES`) name the steps of host code, e.g. the
  cohort scheduler's ``step``.  :func:`span` is off by default: it then
  returns one shared ``contextlib.nullcontext()`` after a single read of a
  module global — no allocation, no clock read, no JAX call.  With a sink
  installed (:func:`set_span_sink`, or ``Telemetry(spans=True)``) each span
  opens a ``jax.profiler.TraceAnnotation`` on the profiler's clock and
  observes its ``time.perf_counter()`` seconds into the sink's histogram
  ``span.<name>`` (count, sum, max).

Turning spans on changes no draw, no order and no output of the code they
wrap; only the sink and the profile see them.
"""

from __future__ import annotations

import contextlib
import time

# the device scopes of the jitted round; a reader charges an operation to
# the outermost of them in its name stack
SCOPES = ("edge_agg", "global_agg", "optimizer", "lm_loss", "embed",
          "mlstm", "slstm", "attention", "mlp", "cross_attention")

# the host spans of CohortScheduler.step
SPAN_NAMES = ("sched.cohort", "sched.channel", "sched.price", "sched.report")

_SINK = None       # MetricsRegistry | None; None = spans off
_OFF = contextlib.nullcontext()


def set_span_sink(registry) -> None:
    """Install (or clear, with None) the registry that spans record into."""
    global _SINK
    _SINK = registry


def get_span_sink():
    return _SINK


class _Span:
    __slots__ = ("name", "reg", "ann", "t0")

    def __init__(self, name: str, reg):
        self.name, self.reg = name, reg

    def __enter__(self):
        import jax

        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.reg.histogram(f"span.{self.name}").observe(dt)
        return False


def span(name: str):
    """Context manager around one step of host code (``name`` from
    :data:`SPAN_NAMES`); the shared null context while spans are off."""
    reg = _SINK
    if reg is None:
        return _OFF
    return _Span(name, reg)


class _Scope(contextlib.ContextDecorator):
    """``jax.named_scope`` that makes a fresh one per use, so a decorated
    function may be re-entered (and traced from several threads)."""

    def __init__(self, name: str):
        self.name = name

    def _recreate_cm(self):
        return _Scope(self.name)

    def __enter__(self):
        import jax

        self.cm = jax.named_scope(self.name)
        return self.cm.__enter__()

    def __exit__(self, *exc):
        return self.cm.__exit__(*exc)


def scope(name: str) -> _Scope:
    """Device scope ``name`` (one of :data:`SCOPES`), as a context manager
    or as a decorator: the operations staged inside carry the name."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; one of {SCOPES}")
    return _Scope(name)
