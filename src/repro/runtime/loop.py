"""Round-loop backend selection: compiled (`scan`) vs generator (`python`).

The protocol primitives in ``core/gmw.py`` are round generators driven by a
Python loop — one interpreter round-trip (and one device dispatch) per
protocol round.  That loop is the reference backend.  The compiled backend
lowers an entire plan replay into ONE jitted XLA program: the round
timeline is static (``core/schedule.py`` predicts it exactly), so the
generators trace straight through ``jax.jit`` and the dense Kogge-Stone
level segment of a solo stream additionally collapses into a genuine
``lax.scan`` over the stacked per-level triples (``gmw._adder_msb_scan``).

Backend choice:

- ``HB_ROUND_LOOP`` unset (default): the compiled fast path wherever the
  comm backend is compatible (see ``compiled_eligible``) and the replay
  is small enough to compile (see ``round_loop_mode``), the generator
  loop elsewhere.
- ``HB_ROUND_LOOP=scan``: the compiled fast path wherever the comm
  backend is compatible, whatever the replay's size.
- ``HB_ROUND_LOOP=python``: generator loop everywhere — the reference
  backend CI runs the tier-1 suite against in addition to ``scan``.

Eligibility: the compiled path bakes the exchange into the program, so the
comm stack must be pure compute with no per-round Python side effects —
exactly ``SimComm`` (local flip) or ``CoalescingComm`` directly over
``SimComm`` (its Python counters fill once at trace time and are
replayed onto the caller's comm by ``api/compile.py``).  Everything else —
``CountingComm``, ``ResilientComm``, ``JournaledComm``,
``FaultInjectingComm``, ``transport.SocketComm`` — needs to observe every
round from Python, and ``MeshComm`` already runs compiled inside
``shard_map`` (one ppermute per fused round; the HLO collective census is
the contract there), so all of those stay on the generator loop.
"""
from __future__ import annotations

import os
from typing import Callable

from repro.core import comm as comm_lib

_VALID = ("scan", "python")


# The compiled replay unrolls every ReLU call of every sibling stream into
# one XLA program, so its compile time and host memory grow with
# (ReLU calls x streams).  Compiled for a TPU v5e on a CPU host: the
# ResNet smoke model (5 calls, one stream) took 57 s and 3.4 GiB, ResNet-18
# (17 calls, one stream) 301 s and 10.8 GiB.  On the one-chip v5e host
# (40 GiB) a one-stream ResNet-18 replay took about 8 minutes to compile,
# and the replay of three requests fused into one batch ran the host out of
# memory.  Larger replays run on
# the generator loop, whose per-round programs stay small.
MAX_COMPILED_RELU_CALLS = 16


def round_loop_mode(relu_calls: Callable[[], int] = lambda: 0) -> str:
    """The round-loop backend for a replay of ``relu_calls()`` ReLU calls,
    summed over its sibling streams: ``"scan"`` (compiled) up to
    ``MAX_COMPILED_RELU_CALLS``, ``"python"`` (generator) beyond.
    ``HB_ROUND_LOOP=scan|python`` overrides the choice, and then
    ``relu_calls`` (which may trace the model) is not called; other
    values are ignored rather than erroring so a typo'd env var cannot
    take down a serving process."""
    mode = os.environ.get("HB_ROUND_LOOP", "")
    if mode in _VALID:
        return mode
    return "scan" if relu_calls() <= MAX_COMPILED_RELU_CALLS else "python"


def compiled_eligible(comm) -> bool:
    """True iff the whole replay may run inside one jitted program on this
    comm backend: exactly SimComm, or CoalescingComm directly over SimComm.
    Subclasses do NOT qualify — a wrapper that adds per-round Python
    behaviour (counters, framing, journaling, sockets) must see every
    round, which the compiled loop by construction does not re-enter
    Python for."""
    if type(comm) is comm_lib.SimComm:
        return True
    return (type(comm) is comm_lib.CoalescingComm
            and type(comm.base) is comm_lib.SimComm)
