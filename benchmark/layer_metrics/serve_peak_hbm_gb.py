"""Device: peak bytes in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
