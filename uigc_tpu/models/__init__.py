from .graphgen import (
    chain_actor_graph,
    kron_actor_graph,
    powerlaw_actor_graph,
    ring_graph,
)

__all__ = [
    "chain_actor_graph",
    "kron_actor_graph",
    "powerlaw_actor_graph",
    "ring_graph",
]
