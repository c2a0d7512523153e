"""Reads BENCHMARK.json and finds, by name, the files a cell is made of."""

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name=None):
    """Import a python file by path (metric readers and references have names
    with dots in them, so they are not importable by module name)."""
    name = name or "bm_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, manifest_path, workload):
        self.manifest_path = os.path.abspath(manifest_path)
        self.root = ROOT     # files are named from the checkout's root
        self.manifest = load_json(self.manifest_path)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"benchmark: no workload {workload!r} in "
                             f"{manifest_path}; have {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfgs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = cfgs[self.entry["config"]]
        self.config_path = os.path.join(self.root, self.config_entry["file"])
        self.config = load_json(self.config_path)
        self.bench_dir = os.path.join(self.root, self.manifest["paths"][0])
        self.traffic_path = os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json")
        self.traffic = load_json(self.traffic_path)

    def reference(self):
        cfg_dir = os.path.dirname(self.config_path)
        return load_module(os.path.join(cfg_dir, self.config["reference"]))

    def adapter(self):
        return load_module(os.path.join(self.bench_dir, "adapters",
                                        self.config["adapter"]))

    def _metrics(self, group):
        out = []
        for m in self.manifest[group]:
            cells = m.get("workloads")
            if cells is None or self.name in cells:
                out.append(m)
        return out

    def end_to_end(self):
        return self._metrics("end_to_end")

    def per_layer(self):
        """Per-layer metrics of this cell: those that list it, and those with
        no list whose end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self._metrics("per_layer") if m["moves"] in mine]

    def reader(self, metric_name):
        """The ``read(ctx)`` of ``metrics/<name>.py``; readers may import
        the helpers beside them (``_flash``, ``_serve``) by name."""
        metrics = os.path.join(self.bench_dir, "metrics")
        if metrics not in sys.path:
            sys.path.insert(0, metrics)
        return load_module(os.path.join(metrics, metric_name + ".py")).read
