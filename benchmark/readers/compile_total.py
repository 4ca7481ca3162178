"""Programs jax built or loaded in the whole run (its monitoring events)."""


def read(rin, key="programs"):
    return float(rin.compiles[key])
