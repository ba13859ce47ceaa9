"""Peak device memory after the window, GiB: bytes in use plus bytes
reserved for program scratch (the compiler's temporaries sit in the second)."""


def read(run):
    return run.device["memory_peak_bytes"] / 2 ** 30
