"""Profiler spans and a compile counter for the serving path.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: with no
profiler session running it costs about a microsecond and records nothing;
under any ``jax.profiler`` capture it lands on the host plane of the same
trace as the device ops, with ``args`` (integers the caller holds) as the
event's stats.  Spans of one request share its ``rid``.

``compiles()`` counts every XLA program compiled (or loaded from the
persistent compilation cache) in this process, from one ``jax.monitoring``
listener registered on import.
"""
from __future__ import annotations

import jax

ADMIT = "serve.admit"                # one popped request: pack, prefill, guard
PREFILL = "serve.prefill"            # ServeEngine.prefill_slot
PREFILL_WAIT = "serve.prefill.wait"  # first-token host sync
GROW = "serve.grow"                  # one page-growth pass before a step
DECODE = "serve.decode"              # ServeEngine.step_slots
DECODE_TABLE = "serve.decode.table"  # page-table build and upload
DECODE_WAIT = "serve.decode.wait"    # emitted-token host sync
RETIRE = "serve.retire"              # token append, retire, expiry
ON_COMPLETE = "serve.on_complete"    # the caller's completion callback
NAMES = (ADMIT, PREFILL, PREFILL_WAIT, GROW, DECODE, DECODE_TABLE,
         DECODE_WAIT, RETIRE, ON_COMPLETE)  # the serving loop's spans
# set-up, outside the loop: ServeEngine encoding its weights to posit words
ENCODE_WEIGHTS = "serve.encode_weights"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0


def _on_duration(event: str, seconds: float, **_) -> None:
    global _compiles
    if event == COMPILE_EVENT:
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> int:
    """Programs compiled or loaded in this process so far."""
    return _compiles


def span(name: str, **args: int) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **args)
