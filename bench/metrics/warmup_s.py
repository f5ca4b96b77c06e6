"""Host seconds of the warm-up alone: loading or compiling each
executable the window dispatches, ending in ``block_until_ready``."""


def read(run):
    return run.warmup_s
