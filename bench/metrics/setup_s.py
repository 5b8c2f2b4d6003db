"""setup_s: seconds from the process start to the start of the window --
generation, store build, analytics init, warm-up and any compilation."""


def read(run):
    return run.setup_s
