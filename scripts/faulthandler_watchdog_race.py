#!/usr/bin/env python3
"""What ends a benchmark run with exit 139 and not one line of output:

    python3 scripts/faulthandler_watchdog_race.py [seconds] [threads]

`faulthandler.dump_traceback_later` starts a watchdog thread that, at
its timeout, walks every thread's frames WITHOUT the interpreter's lock.
Threads whose Python stacks grow and shrink meanwhile (JAX tracing and
lowering a program is such a stack) free the memory under it, and
CPython 3.12 dereferences it.  The watchdog thread blocks every signal,
so the kernel kills the process with SIGSEGV and no handler runs: not
faulthandler's own, not libtpu's, none a test installs.

Until PR 34 `benchmarks/kinds/scrub_passes.py` armed that watchdog
every 0.5 s with a timeout of 1.5 s (`Watch`), so it fired whenever the
event loop stood still that long while other threads worked: the
profiler's hand-over in the middle of a traced pass, or a long compile
under the interpreter's lock (the ledger's PRs 27 and 28, `run_failed`).
No kind arms it now: `benchmarks/stalls.py` writes the stacks from a
thread that holds the interpreter's lock, and `benchmarks/tests/
test_stalls.py` keeps that nothing under `benchmarks/` arms the timed
dump.  This script stays as the witness of the cause.  Needs no chip and
no JAX: here the dumps come every millisecond, and the process dies
within seconds (exit 139)."""

import faulthandler
import sys
import threading
import time


def descend(n, a=1, b=2, c=3, d=4, e=5, f=6, g=7, h=8):
    """A stack deep enough to cross the 16 KiB chunks that CPython keeps
    frames in: chunks are mapped and unmapped as it goes up and down."""
    w, x, y, z = a + b, c + d, e + f, g + h
    return 0 if n == 0 else descend(n - 1, w, x, y, z) + 1


def main() -> int:
    seconds = float(sys.argv[1]) if len(sys.argv) > 1 else 20.0
    threads = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    faulthandler.enable()       # prints nothing when it happens
    stop = time.time() + seconds

    def churn():
        i = 0
        while time.time() < stop:
            i += 1
            descend(150 + i % 300)

    workers = [threading.Thread(target=churn) for _ in range(threads)]
    for t in workers:
        t.start()
    with open("/dev/null", "w") as sink:
        while time.time() < stop:
            faulthandler.dump_traceback_later(0.001, repeat=True, file=sink)
            time.sleep(0.5)
        faulthandler.cancel_dump_traceback_later()
    for t in workers:
        t.join()
    print(f"survived {seconds} s on Python {sys.version.split()[0]}: this "
          f"interpreter's watchdog is safe")
    return 0


if __name__ == "__main__":
    sys.exit(main())
