"""setup_s: host seconds from process start to the window: JAX start-up,
data from the seed, preload, subscribers, warm-up and any compilation."""


def read(run):
    return run.setup_s
