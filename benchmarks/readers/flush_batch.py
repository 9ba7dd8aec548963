"""Requests per coalescer flush: the mean real (pre-padding) batch size of
the window's ``coalescer.device_call`` spans."""


def read(obs, params):
    sizes = [s["attributes"].get("batch.size") for s in obs.get("spans", [])
             if s["name"] == "coalescer.device_call"]
    sizes = [b for b in sizes if b]
    return sum(sizes) / len(sizes) if sizes else None
