"""Tokens of the optimizer steps completed inside the window over the
window's seconds.  The window ends in a device-to-host fetch of the last
step's loss, so every step counted is finished."""


def read(obs):
    t = obs.get("train")
    if not t or not t["steps"]:
        return None
    return t["steps"] * t["tokens_per_step"] / obs["window"]["seconds"]
