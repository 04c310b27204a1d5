"""Finds everything that belongs to one cell by the names in
BENCHMARK.json, so that a new configuration, traffic mix or metric is a
new file plus an entry there and never an edit here.

    workloads[].config  -> configs[].file            (a deployment)
    workloads[].traffic -> traffic/<mix>.json        (parameters only)
    traffic.generator   -> generators/<kind>.py      (one general generator)
    config.archive.kind -> archives/<kind>.py        (seeded data + its reference view)
    end_to_end[].name   -> end_to_end/<name>.py      (read(ctx))
    per_layer[].name    -> layer_metrics/<name>.py   (read(ctx))
"""

import copy
import importlib
import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path):
    with open(path) as fp:
        return json.load(fp)


def load_module(path):
    """A module by file path: metric names hold dots, and a file added
    by a later PR is found without an import statement anywhere."""
    name = "_bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(package, kind):
    if not os.path.isfile(os.path.join(HERE, package, f"{kind}.py")):
        raise SystemExit(f"benchmark: no {package} kind {kind!r}")
    return importlib.import_module(f"benchmarks.{package}.{kind}")


def sized(doc, rehearsal):
    """The data file as it is run: under --rehearsal every group's
    "rehearsal" entry overrides its siblings (tiny sizes that prove the
    script on the CPU), otherwise those entries are dropped."""
    doc = copy.deepcopy(doc)

    def walk(d):
        small = d.pop("rehearsal", None)
        for v in d.values():
            if isinstance(v, dict):
                walk(v)
        if rehearsal and small:
            d.update(small)
    walk(doc)
    return doc


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries that apply here
    per_layer: list


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name, rehearsal=False):
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = sized(load_json(os.path.join(ROOT, cfg_entry["file"])),
                   rehearsal)
    traffic = sized(load_json(os.path.join(
        HERE, "traffic", w["traffic"] + ".json")), rehearsal)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric is reported only where the metric it moves is
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in e2e_names]
    return Cell(name, w["chips"], config, traffic, e2e, layer)


def reader(directory, metric):
    return load_module(os.path.join(HERE, directory, metric + ".py"))
