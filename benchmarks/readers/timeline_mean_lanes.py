"""Mean lanes per device batch: the first dimension of the staged shape
on the `device_timeline` events of one name inside the window."""


def read(window: dict, event: str):
    lanes = [e["args"]["shape"][0] for e in window.get("timeline", [])
             if e.get("name") == event and "shape" in e.get("args", {})]
    if not lanes:
        return None
    return {"value": sum(lanes) / len(lanes), "samples": len(lanes)}
