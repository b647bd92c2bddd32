"""Host-side transport time per device batch, in ms: the link
profiler's seconds (`codec info` → transport → stages) that grew over
the window in the named stages, over the dispatches that grew."""


def read(window: dict, stages, per: str = "dispatch"):
    def of(snap):
        tr = snap["codec_info"].get("transport") or {}
        return tr.get("stages") or {}

    before, after = of(window["before"]), of(window["after"])

    def grown(stage, field):
        return (after.get(stage, {}).get(field, 0)
                - before.get(stage, {}).get(field, 0))

    batches = grown(per, "count")
    if batches <= 0:
        return None
    seconds = sum(grown(s, "seconds") for s in stages)
    return {"value": seconds / batches * 1000.0, "samples": int(batches)}
