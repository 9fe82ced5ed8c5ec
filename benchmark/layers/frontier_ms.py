"""``frontier_ms``: device milliseconds per wake of everything under
``uigc.wake`` that is neither the propagate kernel nor under ``jump``:
``hits``, ``dirty``, ``sat``, ``pack``, ``suspects``, ``gate``, what feeds
the kernel under ``push``, and the two loops' own glue.  Self time of the
wake module's device operations in the traced interval over the
``bench:wake`` spans wholly inside it.  The scope is the
``jax.named_scope`` path that ``ops/pallas_decremental.py`` and
``ops/pallas_trace.py`` give their phases, read from the trace's own event
metadata (``harness/program_trace.py``).  ``kernel_ms`` + ``jump_ms`` +
``frontier_ms`` is the wake module's device time less what carries no
``uigc.wake`` scope (``program_trace.coverage``)."""

from harness.program_trace import scope_ms_per_wake


def read(obs):
    return scope_ms_per_wake(obs, "frontier")
