"""Cells of the benchmark cut to a size a CPU test run holds: the same
files, made small by the cell's family (its ``shrink``)."""
from bench import spec


def cell(name: str, **over) -> spec.Cell:
    """The named cell of BENCHMARK.json at a tiny size; ``over``
    replaces config keys."""
    return shrink(spec.cell(name), **over)


def shrink(c: spec.Cell, **over) -> spec.Cell:
    """``c`` at a tiny size; ``over`` replaces config keys."""
    return c.family.shrink(c, **over)
