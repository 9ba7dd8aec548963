"""Units of work completed in the window over the whole window (first
dispatch to the last step's ``block_until_ready``)."""


def read(obs, params):
    if not obs.get("work_done") or not obs.get("work_window_s"):
        return None
    return obs["work_done"] / obs["work_window_s"]
