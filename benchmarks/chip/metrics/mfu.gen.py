"""Model FLOPs of the serving window (prefill and decode, from count.py)
over the traced window times the chip's bf16 peak."""

import count


def read(rec):
    if rec["traffic"]["kind"] != "serve":
        return None
    m, w = rec["model"], rec["work"]
    flops = sum(count.prefill_flops(m, w["batch"], n) for n in w["prefills"])
    flops += sum(count.decode_flops(m, w["batch"], pos) for pos in w["decode_positions"])
    return 100.0 * flops / (rec["trace"]["window_s"] * rec["peaks"]["bf16_flops"])
