"""Benchmark harness: auto-discovers every ``bench_*.py`` module in this
package (one per paper table/figure or engine subsystem) and runs its
``run()`` entry point.  New benchmarks are picked up by existence — there
is no registration list to forget.  Prints CSV lines
(``name,key=value,...``); exits non-zero if any benchmark raised."""

from __future__ import annotations

import importlib
import pkgutil
import sys
import time

import benchmarks
from benchmarks.common import force_host_devices
from repro.launch.compile_cache import use_compile_cache

PREFIX = "bench_"


def discover() -> list[str]:
    """Module names of every bench_*.py file, sorted.  Import happens
    per-benchmark inside the harness try block, so one broken module
    cannot take down the others."""
    return sorted(info.name for info in pkgutil.iter_modules(
        benchmarks.__path__)
        if info.name.startswith(PREFIX) and not info.ispkg)


def main() -> None:
    force_host_devices()
    use_compile_cache()
    failed = 0
    for modname in discover():
        name = modname[len(PREFIX):]
        print(f"# ---- {name} ----", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{modname}")
            fn = getattr(mod, "run", None)
            if not callable(fn):
                raise AttributeError(f"{modname} has no run() entry point")
            fn()
        except Exception as e:  # keep the harness running
            failed += 1
            print(f"{name},ERROR,{e!r}")
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
