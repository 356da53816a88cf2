"""Process start to the first measured request."""


def read(run, args):
    return run.setup_s
