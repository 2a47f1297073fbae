"""The whole training step's share of the chips' peak: model FLOPs of the
window's steps (``harness.flops``, no recomputation) over the window's
seconds times the peak of ``harness/peaks.json``, in percent."""


def read(r):
    w = r["window"]
    if not w.get("steps") or not w.get("seconds"):
        return None
    done = w["steps"] * r["flops_per_step"]
    return 100.0 * done / (w["seconds"] * r["peak_flops"] * r["chips"])
