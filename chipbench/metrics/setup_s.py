"""Set-up: from process start to the window's opening (imports, weights
from the seed, warm-up and compilation, the checked training steps)."""


def read(run):
    return run.setup_s
