"""Process start to the window's opening (host clock): imports, weights,
compiling or loading the compiled programs, and the warm-up call."""


def read(run):
    return run.setup_s
