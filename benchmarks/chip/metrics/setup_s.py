"""From process start to the window's start (host clock): fixtures, the
worker, the presampled epoch, compiles and the rehearsal steps."""


def read(run: dict) -> float | None:
    return run["setup_s"]
