"""Median per step of the program's ``engine.upload`` span: ``device_put``
of the step's layers and padded input, waited for, from the traced run's
host spans."""
import hostspans


def read(run: dict) -> float | None:
    return hostspans.median_ms(run, "engine.upload")
