"""Host seconds from the process's start to the window's: importing,
device start-up, trace synthesis, and loading or compiling every
executable the window dispatches."""


def read(run):
    return run.setup_s
