"""Bytes of the working copy as held on the device, per token of one decode
step (divided by the batch), in MB: what the movement layer hands each
generated token.  A count read from the arrays at set-up."""


def read(rec):
    if rec["traffic"]["kind"] != "serve":
        return None
    return rec["weight_bytes"] / rec["work"]["batch"] / 1e6
