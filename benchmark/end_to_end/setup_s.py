"""Seconds from the moment the backend is up (``jax.devices()`` has returned)
to the opening of the measured window: the program's imports, weights, the
engine, compilation or the load from the compile cache, warm-up and, when
serving, the ramp.  The backend's own start is logged and not counted."""


def read(obs):
    return obs["setup_s"]
