"""``compiles_in_window``: backend compile requests that ended inside the
window, loads from the persistent cache included (``jax.monitoring``).
Every shape is warmed in set-up, so this is 0 unless the program builds a
new program per wake."""


def read(obs):
    return obs.facts.get("compile_requests")
