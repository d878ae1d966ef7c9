"""train_mfu: model FLOPs per token (bench/harness/flops.py, recomputation
not counted) times tokens per second over the window, over the chips' bf16
peak (bench/peaks.json). A ratio of 1 is the peak."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    rate = ctx["tokens"] / ctx["window_s"]
    return ctx["flops_per_token"] * rate / (ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"])
