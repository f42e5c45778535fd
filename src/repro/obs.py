"""Tracing of the round programs: device phase scopes, host spans and the
host-copy counter, all read from one ``jax.profiler`` trace.

- ``phase(name)`` is ``jax.named_scope("repro.<name>")``: each phase of a
  round wraps its code in it once, so every compiled op of the phase
  carries ``repro.<name>`` in its ``op_name`` metadata. Metadata only;
  the numerics and the compiled schedule do not change. Phases do not
  nest.
- ``block(name)`` is ``jax.named_scope("repro.block.<name>")`` around one
  block of the model's forward (a Mamba layer, a hybrid call of a shared
  block): inside ``repro.grad`` on the round's path, never a phase of its
  own. Metadata only, as for phases.
- ``span(name)`` is ``jax.profiler.TraceAnnotation("repro.<name>")``
  around a step of a host loop: written into the trace, on the device
  ops' clock, while the profiler runs; about a microsecond otherwise.
- ``fetch(x)`` is ``np.asarray(x)`` counted in ``host_copies``, the
  process-wide number of device-to-host copies the engine's host loop
  has made.
"""
from __future__ import annotations

import jax
import numpy as np

PREFIX = "repro."
# device phases of a round (named scopes)
PHASES = ("schedule", "grad", "codec", "mac_decode", "optim")
# kinds of model block (named scopes under the grad phase)
BLOCKS = ("mamba", "shared")
# steps of the engine's host loop (host spans)
SPANS = ("init", "dispatch", "fetch", "eval")

host_copies = 0


def phase(name: str):
    """Named scope ``repro.<name>`` around one phase of a round."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; phases are {PHASES}")
    return jax.named_scope(PREFIX + name)


def block(name: str):
    """Named scope ``repro.block.<name>`` around one block of the model."""
    if name not in BLOCKS:
        raise ValueError(f"unknown block {name!r}; blocks are {BLOCKS}")
    return jax.named_scope(PREFIX + "block." + name)


def span(name: str):
    """Host span ``repro.<name>`` around one step of a host loop."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; spans are {SPANS}")
    return jax.profiler.TraceAnnotation(PREFIX + name)


def fetch(x) -> np.ndarray:
    """``np.asarray(x)``, counted in ``host_copies``."""
    global host_copies
    host_copies += 1
    return np.asarray(x)
