"""Jitted steps: programs lowered inside the measured window (JAX's own
monitoring events).  Has to be 0."""


def read(obs):
    return obs.get("compiles_in_window")
