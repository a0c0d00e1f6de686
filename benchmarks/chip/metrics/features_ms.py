"""Median per step of the program's ``worker.features`` and
``worker.resolve`` spans: the cache probe, the device tier's gather and
its copy back, and the host rows with the hits laid over them, from the
traced run's host spans."""
import hostspans


def read(run: dict) -> float | None:
    return hostspans.median_ms(run, "worker.features", "worker.resolve")
