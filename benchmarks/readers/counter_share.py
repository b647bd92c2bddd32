"""A counter's growth over the window as a share, in %, of the growth of
a group of counters, on the node under test (`/metrics` text).
`part` and `whole` are lists of [family, {label: value}]."""

from benchmarks.cluster import metric_sum


def read(window: dict, part, whole):
    def grown(series):
        return sum(
            metric_sum(window["after"]["metrics"], fam, **labels)
            - metric_sum(window["before"]["metrics"], fam, **labels)
            for fam, labels in series)

    den = grown(whole)
    if den <= 0:
        return None
    return {"value": 100.0 * grown(part) / den, "samples": int(den)}
