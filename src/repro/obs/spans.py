"""Host spans of the executor chain, on the JAX profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it lands on the
``/host:CPU`` plane of the same trace as the device's operations, on
one clock, so a reader of the trace can tell what the host was doing
while the device sat idle. When no profiler is tracing, :func:`span`
returns a shared no-op context after one flag check.

Arguments ride in the span's name (``n3h.layer#layer=conv1#``, the
profiler's own encoding, which it splits into the event's name and
stats). Callers build such names once, with :func:`encode`, when the
executor is built, not on every call.

The spans, outermost first (see ``docs/observability.md``):

* ``n3h.run`` — one input through the whole chain (an executor's
  ``run``);
* ``n3h.run.launch`` — ``PallasExecutor.run``'s enqueue of the
  program's one ``n3h_chain`` executable;
* ``n3h.layer`` (arg ``layer``) — all work for one layer of the chain
  (``runtime/base.py`` ``chain_layers``);
* ``n3h.layer.run`` — the chain's call to the backend's ``run_layer``;
* ``n3h.layer.launch`` (arg ``path``) — the enqueue of the layer's
  jitted kernel or fallback (``PallasExecutor.run_layer``);
* ``n3h.layer.glue`` — the chain's eager glue: staging, reshapes,
  scaling, dequantizing a residual, requantizing an FC hand-off;
* ``n3h.layer.tail`` — the enqueue of the layer's elementwise tail;
* ``n3h.decode.step`` / ``n3h.decode.step_slots`` (arg ``phase``) —
  one decode step of an ``ExecutorSession``.

The per-layer ``n3h.layer*`` spans open on the eager chains (golden,
multi-device, ``PallasExecutor`` with ``check_timing=True`` or a
replaced ``run_layer``), and once, inside ``n3h.run.launch``, while a
program's chain executable is traced; a warm ``PallasExecutor.run``
opens ``n3h.run`` and ``n3h.run.launch`` alone.

Spans of one name never overlap each other, and all are opened on the
caller's thread.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

RUN = "n3h.run"
RUN_LAUNCH = "n3h.run.launch"
LAYER = "n3h.layer"
LAYER_RUN = "n3h.layer.run"
LAYER_LAUNCH = "n3h.layer.launch"
LAYER_GLUE = "n3h.layer.glue"
LAYER_TAIL = "n3h.layer.tail"
DECODE_STEP = "n3h.decode.step"
DECODE_STEP_SLOTS = "n3h.decode.step_slots"

_OFF = contextlib.nullcontext()
_tracing = TraceAnnotation.is_enabled


def encode(name: str, **args) -> str:
    """``name`` with ``args`` in the profiler's encoding, which the
    trace splits back into the event's name and its stats. Values must
    not hold ``#``, ``,`` or ``=``."""
    if not args:
        return name
    return name + "#" + ",".join(f"{k}={v}" for k, v in args.items()) + "#"


def span(name: str):
    """A host span named ``name`` (a name from :func:`encode` carries
    its arguments), or a shared no-op context when no profiler is
    tracing."""
    return TraceAnnotation(name) if _tracing() else _OFF


def layer_spans(layers) -> tuple:
    """The ``n3h.layer`` span name of each layer, in chain order."""
    return tuple(encode(LAYER, layer=lp.name) for lp in layers)
