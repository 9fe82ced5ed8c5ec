"""``kernel_ms``: device seconds of the propagate kernel per wake: the
summed durations of the Mosaic custom-call events in the traced interval
over the wakes that lie wholly inside it.  The ``pallas_call`` has no
name of its own (``ops/pallas_trace.py build_propagate``), so the events
are found under the name XLA gives a TPU custom call."""

#: what the kernel's events are named in the device plane's op line
#: today: the HLO text of a TPU custom call, `%body.9 = f32[...]
#: custom-call(...)`
KERNEL_EVENT = r" custom-call\("


def read(obs):
    trace = obs.trace
    if trace is None:
        return None
    seconds, events = trace.seconds_of(KERNEL_EVENT)
    wakes = trace.spans_inside("wake")
    if not events or not wakes:
        return None
    return seconds * 1e3 / wakes
