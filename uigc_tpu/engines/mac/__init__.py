from .detector import ACK, BLK, UNB, Audit, CycleDetector
from .engine import MAC, MacRefob, MacState, RC_INC

__all__ = [
    "ACK",
    "Audit",
    "BLK",
    "CycleDetector",
    "MAC",
    "MacRefob",
    "MacState",
    "RC_INC",
    "UNB",
]
