"""Device time of the decode program per step (``jit_decode_step`` in the
trace's XLA Modules), in ms."""


def read(rec):
    p = rec["trace"]["programs"].get("jit_decode_step")
    if not p or not p["n"]:
        return None
    return 1e3 * p["device_s"] / p["n"]
