"""Trained tokens (loss_mask 1 in the rows the window's steps consumed)
over the window's whole length."""


def read(run):
    c = run.counters
    if "trained_tokens" not in c or run.window_s <= 0:
        return None
    return c["trained_tokens"] / run.window_s
