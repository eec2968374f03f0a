"""setup_s: process start to the first timed request (s): JAX start, the
deployment built from the seed, and every pooled request served once."""


def read(run):
    return run.setup_s
