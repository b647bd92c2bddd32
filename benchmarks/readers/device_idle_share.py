"""Share of the traced window, in %, in which no operation ran on the
device: 1 − (union of device-op intervals ÷ window), from the
profiler's trace (`benchmarks/trace_reduce.py`)."""


def read(window: dict):
    red = window.get("trace")
    if not red or red["window_s"] <= 0 or red["device_planes"] == 0:
        return None
    return {"value": 100.0 * (1.0 - red["busy_s"] / red["window_s"]),
            "samples": red["device_planes"]}
