"""Process start to the window's start: data, build and warm-up."""


def read(run):
    return run.setup_s
