"""``jump_double_ms.chain``: device milliseconds per wake under the scope
``jump/double``: the pointer doublings of ``pallas_trace.jump_sweep``
(2 gathers over all actors a doubling, ``JUMP_STEPS`` doublings a jump
sweep), apart from the gather of the parents' bits (``jump/hits``) and the
pack of the hits (``jump/pack``).  Self time of the wake module's device
operations in the traced interval over the ``bench:wake`` spans wholly
inside it (``harness/program_trace.py``).  Nothing on a program whose
``jump`` scope is one lump."""

from harness.program_trace import program_trace

SCOPE = "jump/double"


def read(obs):
    trace = program_trace(obs)
    if trace is None or obs.trace is None or not trace.has_scopes:
        return None
    wakes = obs.trace.spans_inside("wake")
    seconds = trace.seconds(lambda op: not op.is_kernel and op.under(SCOPE))
    if not wakes or seconds <= 0:
        return None
    return seconds * 1e3 / wakes
