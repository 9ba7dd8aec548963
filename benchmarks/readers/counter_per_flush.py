"""One of the program's counters over the window, a flush: its increase
between the window's two readings over the window's ``coalescer.device_call``
spans. Nothing where the program has no such counter (it then reads 0 at
both ends) or the run kept no spans."""


def read(obs, params):
    count = (obs.get("counters") or {}).get(params["counter"])
    flushes = sum(1 for s in obs.get("spans", [])
                  if s["name"] == "coalescer.device_call")
    if not count or not flushes:
        return None
    return count / flushes
