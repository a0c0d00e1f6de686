"""Median per step of the program's ``engine.build`` span: the numpy build
of the block-sparse 128x128 tiles and their power-of-two bucketing
(``ComputeEngine._prepare``), from the traced run's host spans."""
import hostspans


def read(run: dict) -> float | None:
    return hostspans.median_ms(run, "engine.build")
