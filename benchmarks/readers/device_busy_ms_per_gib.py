"""Device busy time per GiB of block data the window verified, in ms:
the union of device-op intervals of the profiler's trace over the bytes
of the passes it holds (`bytes_traced`)."""


def read(window: dict):
    red = window.get("trace")
    nbytes = window.get("bytes_traced")
    if not red or not nbytes or red["busy_s"] <= 0:
        return None
    return {"value": red["busy_s"] * 1000.0 / (nbytes / 2**30),
            "samples": int(nbytes)}
