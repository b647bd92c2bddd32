"""Growth of a group of counters over the window over the growth of
another group, times `scale`, on the node under test (`/metrics` text).
`num` and `den` are lists of [family, {label: value}], as
`counter_share` takes them; without `den` the value is the growth of
`num` itself.  A denominator that did not grow is nothing to read
(None), as on a program that lacks the counters; a numerator that did
not grow beside one that did is the value 0."""

from benchmarks.cluster import metric_sum


def read(window: dict, num, den=None, scale: float = 1.0):
    def grown(series):
        return sum(
            metric_sum(window["after"]["metrics"], fam, **labels)
            - metric_sum(window["before"]["metrics"], fam, **labels)
            for fam, labels in series)

    top = grown(num)
    if den is None:
        return ({"value": top * scale, "samples": int(top)} if top > 0
                else None)
    bottom = grown(den)
    if bottom <= 0:
        return None
    return {"value": top / bottom * scale, "samples": int(bottom)}
