"""``closure_ms``: device milliseconds under ``uigc.wake/closure`` (the closure loop with its kernel calls), per wake: self time of the wake
module's device operations in the traced interval over the ``bench:wake``
spans wholly inside it.  The scope is the ``jax.named_scope`` path that
``ops/pallas_decremental.py`` and ``ops/pallas_trace.py`` give their
phases, read from the trace's own event metadata
(``harness/program_trace.py``)."""

from harness.program_trace import scope_ms_per_wake


def read(obs):
    return scope_ms_per_wake(obs, "closure")
