"""Share, in %, of the seconds of the `device_timeline` events of one
name that carry `arg` == `value`, over the seconds of all events of
that name that carry `arg` at all.  Where none carries it (a program
that does not write the argument) there is nothing to read; where some
do and none has the value, the share is 0."""


def read(window: dict, event: str, arg: str, value):
    tagged = [e for e in window.get("timeline", [])
              if e.get("name") == event
              and e.get("args", {}).get(arg) is not None]
    whole = sum(e.get("dur", 0) for e in tagged)
    if whole <= 0:
        return None
    part = sum(e.get("dur", 0) for e in tagged if e["args"][arg] == value)
    return {"value": 100.0 * part / whole, "samples": len(tagged)}
