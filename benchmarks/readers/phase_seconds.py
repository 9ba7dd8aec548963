"""Host-clock seconds of one named phase of set-up."""


def read(obs, params):
    hits = [s for n, s in obs.get("phases", []) if n == params["phase"]]
    return sum(hits) if hits else None
