"""Seconds from process start to the window's start: imports, device and compile-cache
set-up, the seeded batches, the engine, and the warm-up that fills the window."""


def read(run):
    return run.setup_s
