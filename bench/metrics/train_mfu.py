"""Model FLOP/s of the training window over the chip's bf16 peak (%).

Model FLOPs per step come from ``counts.py`` (from shapes; recomputation not
counted), steps and seconds from the window's host clock."""


def read(ctx):
    f = ctx["facts"]
    if "model_flops_per_step" not in f or not f.get("steps"):
        return None
    rate = f["model_flops_per_step"] * f["steps"] / f["window_s"]
    return 100.0 * rate / (ctx["peaks"]["bf16_flops"] * ctx["trace"]["chips"])
