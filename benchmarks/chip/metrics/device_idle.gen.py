"""Share of the traced serving window in which no operation ran on the
device: 1 - busy union / window, from the profiler trace."""


def read(rec):
    if rec["traffic"]["kind"] != "serve":
        return None
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
