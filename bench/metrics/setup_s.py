"""Process start to window open: model and weights, engine, the warm-up
that loads or compiles every program the window can call."""


def read(run):
    return run.setup_s
