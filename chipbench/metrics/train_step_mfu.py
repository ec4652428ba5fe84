"""Model FLOPs of the samples trained in the traced window per second, over
the chips' peak: 6 H C + 4 nnz H per sample (``work.model_flops_per_sample``);
evaluation is not counted."""


def read(t, record):
    w = t["work"]
    if t["window_s"] <= 0 or w["model_flops"] <= 0:
        return None
    return 100.0 * w["model_flops"] / t["window_s"] / (w["chips"] * w["peak_flops"])
