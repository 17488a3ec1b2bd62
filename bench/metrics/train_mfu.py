"""The whole step's share of the chip's bf16 peak, in %: three forward
passes' operations (`bench/flops.py`) per image trained in the traced
window's grants, over the traced window's seconds x chips x peak."""
import flops


def read(run):
    if run.trace is None or run.peaks is None or run.trace_start is None:
        return None
    traced = [g for g in run.grants if g["start"] >= run.trace_start
              and g["start"] < run.seconds]
    images = sum(g["width"] for g in traced) * run.images_per_update
    fr = run.cfg["frame"]
    work = images * flops.train_flops_per_image(run.cfg["student"],
                                                fr["height"], fr["width"])
    return 100 * work / (run.trace["window_s"] * run.chips
                         * run.peaks["flops_bf16"])
