"""Share of the window, in %, covered by the union of the
`device_timeline` events whose name starts with `prefix`; with
`uncovered`, the share that no such event covers.  The window is the
two snapshots' stamps on the ring's own clock (`mono_us`).  No such
event in the window is nothing to read."""


def read(window: dict, prefix: str, uncovered: bool = False):
    lo, hi = window["before"]["mono_us"], window["after"]["mono_us"]
    spans = sorted((max(e["ts"], lo), min(e["ts"] + e.get("dur", 0), hi))
                   for e in window.get("timeline", [])
                   if e.get("name", "").startswith(prefix))
    spans = [(s, e) for s, e in spans if e > s]
    if not spans or hi <= lo:
        return None
    covered, end = 0, lo
    for s, e in spans:
        if e > end:
            covered += e - max(s, end)
            end = e
    share = covered / (hi - lo)
    return {"value": 100.0 * (1.0 - share if uncovered else share),
            "samples": len(spans)}
