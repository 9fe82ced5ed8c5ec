"""``gather_ms.mesh4``: device milliseconds per wake inside the sharded wake's ``gather`` scope (``uigc.wake/<phase>/gather``:
``jax.named_scope`` around ``gather_table``, the all-gather of the packed table and the pad and reshape behind it, in
``uigc_tpu/parallel/sharded_trace.py``), self seconds of the wake module's operations, the maximum over the device planes
(a collective ends when its last shard arrives, so the slowest plane's time holds the others' waiting), per ``bench:wake``
span inside the traced interval.  Nothing on a program without the scope."""

from harness.mesh_trace import scope_ms_slowest_plane


def read(obs):
    return scope_ms_slowest_plane(obs, "gather")
