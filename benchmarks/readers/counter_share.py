"""One of the program's counters over the window as a share, in percent,
of (the window's flushes × a size of the cell, ``params["of"]``): e.g. the
rows a flush read of all the items. Nothing where the program has no such
counter (it reads 0 at both ends) or the run kept no spans."""


def read(obs, params):
    count = (obs.get("counters") or {}).get(params["counter"])
    flushes = sum(1 for s in obs.get("spans", [])
                  if s["name"] == "coalescer.device_call")
    if not count or not flushes:
        return None
    return 100.0 * count / (flushes * obs["sizes"][params["of"]])
