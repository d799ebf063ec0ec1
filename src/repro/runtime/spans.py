"""Named spans of the served path, on the JAX profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: it records
a host event only while a profile is being captured (``jax.profiler.
start_trace`` or a profiler server's capture), so the spans share the
device trace's clock; otherwise the cost is the profiler's own check
that no capture is active.  ``args`` (request ids, ReLU group, round
number) become the event's stats, not part of its name.  A span nests
only within its thread, and never stays open across a generator
``yield``.

Code traced into a jitted program uses ``jax.named_scope`` with the same
name instead, so the compiled device ops carry it; a host span opened
while a jit traces fires once, at trace time.

The vocabulary (``NAMES``), outermost first; ``docs/serving.md`` says
what each one bounds:

- ``frontend.decode``, ``frontend.infer``, ``frontend.wait``,
  ``frontend.reveal``, ``frontend.encode``: the HTTP frontend;
- ``engine.submit``, ``engine.execute``: admission, and one micro-batch's
  plan replay;
- ``linear`` (children ``linear.encode``, ``linear.matmul``,
  ``linear.truncate``): a linear layer on public weights;
- ``relu.layer``: one ReLU call of the replay; ``beaver.triples``: its
  Beaver triples;
- ``round.local``: one stream's local protocol work between exchanges;
  ``round.exchange``: one fused round's exchange;
- ``relu.a2b``, ``relu.adder``, ``relu.b2a``, ``relu.mult``: the local
  segments of the ReLU protocol's phases, inside ``round.local``.
"""
from __future__ import annotations

import jax

NAMES = ("frontend.decode", "frontend.infer", "frontend.wait",
         "frontend.reveal", "frontend.encode", "engine.submit",
         "engine.execute", "linear", "linear.encode", "linear.matmul",
         "linear.truncate", "relu.layer", "beaver.triples", "round.local",
         "round.exchange", "relu.a2b", "relu.adder", "relu.b2a",
         "relu.mult")


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **args)
