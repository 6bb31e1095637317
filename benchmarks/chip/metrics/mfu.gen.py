"""Model FLOPs of the serving window (prefill and decode, by the counts of
the cell's model-files module) over the traced window times the chip's
bf16 peak."""


def read(rec):
    if rec["traffic"]["kind"] != "serve":
        return None
    mf, m, w = rec["model_files"], rec["model"], rec["work"]
    flops = sum(mf.prefill_flops(m, w["batch"], n) for n in w["prefills"])
    flops += sum(mf.decode_flops(m, w["batch"], pos) for pos in w["decode_positions"])
    return 100.0 * flops / (rec["trace"]["window_s"] * rec["peaks"]["bf16_flops"])
