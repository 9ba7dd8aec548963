"""Process start to the first timed request or step, compile included."""


def read(obs, params):
    return obs.get("setup_s")
