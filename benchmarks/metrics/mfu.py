"""The whole window's share of the chip's bf16 peak: forward+backward
FLOPs per sample from shapes (``configs/<name>.py train_flops_per_sample``,
no recomputation counted) x window samples / window wall / chips / peak."""


def read(run):
    if not run["peaks"] or not run["window_s"]:
        return None
    flops = run["train_flops_per_sample"] * run["samples"]
    peak = run["peaks"]["bf16_tflops"] * 1e12 * run["chips"]
    return 100.0 * flops / run["window_s"] / peak
