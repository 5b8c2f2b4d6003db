"""fresh_s: the window, to the end of the round that crosses its deadline,
over the update batches served in it -- the time from one batch to both
of its analytics read fresh."""


def read(run):
    if not run.requests("property"):
        return None
    return run.window_s / len(run.requests("update"))
