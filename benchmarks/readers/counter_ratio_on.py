"""`counter_ratio` over the counters of a node the cell's kind names,
and only where the program has the families it is asked for.

`on` is a key of the window under which the kind left `before` and
`after` snapshots of another node than the one under test (`endpoint`:
the node whose S3 endpoint took the window's requests); without it the
node under test's are read.  `needs` lists families that have to stand
in the `after` snapshot at all (a registered counter stands there at 0):
a program that lacks one gives nothing to read (None), never 0.  `num`,
`den` and `scale` are `counter_ratio`'s."""

import pathlib

from benchmarks import harness

counter_ratio = harness.load_module(
    pathlib.Path(__file__).with_name("counter_ratio.py"),
    "bench_reader_counter_ratio_under_on")


def read(window: dict, num, den, scale: float = 1.0, on: str = None,
         needs=()):
    where = window if on is None else window.get(on)
    if where is None:
        return None
    families = {series.partition("{")[0]
                for series in where["after"]["metrics"]}
    if not families.issuperset(needs):
        return None
    return counter_ratio.read(where, num, den, scale)
