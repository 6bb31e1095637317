"""Share of the decode program's device time that the chip's roofline says
the decode steps need: the sum over steps of max(FLOPs / peak FLOP/s,
bytes / HBM bytes/s), by the counts of the cell's model-files module and
peaks.py, over the device time of ``jit_decode_step``.  Decode at these
batch sizes is bound by bytes."""


def read(rec):
    p = rec["trace"]["programs"].get("jit_decode_step")
    positions = rec["work"]["decode_positions"]
    if not p or not p["device_s"] or p["n"] != len(positions):
        return None
    mf, m, b, pk = rec["model_files"], rec["model"], rec["work"]["batch"], rec["peaks"]
    need = sum(max(mf.decode_flops(m, b, pos) / pk["bf16_flops"],
                   mf.decode_bytes(m, b, pos) / pk["hbm_bytes_per_s"])
               for pos in positions)
    return 100.0 * need / p["device_s"]
