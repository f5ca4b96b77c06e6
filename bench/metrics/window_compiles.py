"""Executables the program built (``engine.compile_count()``) while the
window ran: 0 when the warm-up covered every shape and chunk width."""


def read(run):
    return run.window.compiles
