from .trace import (
    FLAG_BUSY,
    FLAG_HALTED,
    FLAG_IN_USE,
    FLAG_INTERNED,
    FLAG_LOCAL,
    FLAG_ROOT,
    garbage_and_kills_np,
    pseudoroots_np,
    trace_marks_np,
)

__all__ = [
    "FLAG_BUSY",
    "FLAG_HALTED",
    "FLAG_IN_USE",
    "FLAG_INTERNED",
    "FLAG_LOCAL",
    "FLAG_ROOT",
    "garbage_and_kills_np",
    "pseudoroots_np",
    "trace_marks_np",
]
