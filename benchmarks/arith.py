"""Metric arithmetic: the reduction from samples to the numbers printed."""


def rate(amount: float, seconds: float) -> float:
    """All the work over all the time of the window, stalls included."""
    if seconds <= 0:
        raise ValueError("rate over no time")
    return amount / seconds
