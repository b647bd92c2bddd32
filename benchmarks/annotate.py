"""Spans of the benchmark's own around calls into the program's layers,
for traced runs: `annotations.json` names the functions.  Written from
here, so the program carries no new span for them (that is for the
tracing issue)."""

import functools
import importlib

from benchmarks.harness import HERE, load_json, log


def install(path=HERE / "annotations.json") -> int:
    import jax.profiler

    done = 0
    for module, qualname, label in load_json(path)["targets"]:
        try:
            owner = importlib.import_module(module)
            *parents, name = qualname.split(".")
            for p in parents:
                owner = getattr(owner, p)
            fn = getattr(owner, name)
        except (ImportError, AttributeError):
            log(f"annotation target missing: {module}:{qualname}")
            continue

        def wrap(fn, label):
            @functools.wraps(fn)
            def annotated(*a, **kw):
                with jax.profiler.TraceAnnotation(f"bench:{label}"):
                    return fn(*a, **kw)
            return annotated

        setattr(owner, name, wrap(fn, label))
        done += 1
    return done
