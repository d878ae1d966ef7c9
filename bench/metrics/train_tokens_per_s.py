"""train_tokens_per_s: every token trained in the window over the window's
wall time, host gaps between steps included (host clock)."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return ctx["tokens"] / ctx["window_s"]
