"""Model FLOP utilization of the traced window: the forward and
backward FLOPs of the local gradient steps completed in it (the
configuration's ``bench/models/<model>.py``, recomputation not counted)
over window x chips x the chip's peak."""


def read(rec):
    if rec.get("window_s", 0) <= 0 or not rec.get("model_flops"):
        return None
    return 100.0 * rec["model_flops"] / (
        rec["window_s"] * rec["chips"] * rec["peaks"]["flops"])
