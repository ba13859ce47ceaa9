"""Process start to the window's opening (its first due request), s."""


def read(run):
    return run.setup_s
