"""Model operations per trained token (from shapes, no recompute) times
trained tokens per second, over the chips' bf16 peak."""


def read(run):
    c = run.counters
    if "trained_tokens" not in c or run.window_s <= 0:
        return None
    rate = c["trained_tokens"] / run.window_s
    return 100.0 * c["flops_per_token"] * rate / c["peak_flops"]
