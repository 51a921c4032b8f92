"""Find a cell's parts by name, so that a new cell is files and entries.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
each per-layer metric.  Their files sit at fixed places under
``perfbench/``:

* ``configs/<config>.json`` - the configuration as it is run, and
  ``configs/<config>.py`` beside it: the client that makes its requests
  and the plain reference that judges the answers;
* ``traffic/<mix>.json`` - the parameters the one generator reads;
* ``metrics/<metric>.py`` - a reader with ``read(ctx)``, for the
  end-to-end metrics and the per-layer ones alike;
* ``work/<op>.py`` - the bytes one request's work moves;
* ``peaks.json`` - published peaks by ``device_kind``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything it names, loaded."""

    name: str
    chips: int
    config: dict          # configs/<config>.json
    client: object        # the module configs/<config>.py
    traffic: dict         # traffic/<mix>.json
    work: object          # the module work/<op>.py
    end_to_end: list      # [(entry, reader module)] this cell reports
    per_layer: list       # the same, of its per-layer metrics
    peaks: dict           # peaks.json


def load_module(path: str):
    """Import one file as a module, whatever characters its name has."""
    name = "perfbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, *, root: str, bench_dir: str = HERE) -> Cell:
    """Resolve cell ``name`` of ``<root>/BENCHMARK.json`` to its files
    under ``bench_dir``; raises ``KeyError`` for an unknown cell."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    config = _json(os.path.join(bench_dir, "configs", w["config"] + ".json"))
    client = load_module(os.path.join(bench_dir, "configs",
                                      w["config"] + ".py"))
    traffic = _json(os.path.join(bench_dir, "traffic",
                                 w["traffic"] + ".json"))
    work = load_module(os.path.join(bench_dir, "work",
                                    config["op"] + ".py"))

    def readers(kind: str) -> list:
        return [(m, load_module(os.path.join(bench_dir, "metrics",
                                             m["name"] + ".py")))
                for m in bench[kind] if _applies(m, name)]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                client=client, traffic=traffic, work=work,
                end_to_end=readers("end_to_end"),
                per_layer=readers("per_layer"),
                peaks=_json(os.path.join(bench_dir, "peaks.json")))


def peak(peaks: dict, device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return peaks[device_kind]
