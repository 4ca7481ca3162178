"""Model FLOP/s utilisation: the FLOPs that forward and backward require
per item (from shapes, by the reference's own function) x items/s over
chips x the published peak. Recomputed operations do not count."""


def read(rin):
    w = rin.work
    if "flops_per_item" not in w:
        return None
    peak = rin.peaks["bf16_tflops"] * 1e12 * w["chips"]
    return 100.0 * w["flops_per_item"] * w["items_s"] / peak
