"""Process start to the start of the window: data and weights made, programs
compiled or read from the cache, the first steps taken. Host clock."""


def read(run):
    return run.setup_s
