"""BENCHMARK.json and the files it names, all found by name.

A cell names a configuration and a traffic mix; the mix names its driver;
a per-layer metric names its reader. Each lives in a file of its own:

    <root>/BENCHMARK.json
    <root>/<configs[].file>                       one configuration
    <dir>/traffic/<traffic>.json                  one traffic mix
    <dir>/drivers/<driver>.py                     run(ctx) -> result
    <dir>/layer_metrics/<metric>.py               read(facts) -> number|None
    <dir>/families/<family>.py                    build_model(cfg)
    <dir>/reference/<family>.py                   the plain reference

``<dir>`` is searched in ``<root>/<paths[0]>`` first and then in this
package, so a checkout (or a test's temporary directory) that holds only
data files still finds the code that is here.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)


class Manifest:
    def __init__(self, root: str = REPO_ROOT):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self._modules = {}
        self.dirs = []
        for d in (os.path.join(self.root, self.data["paths"][0]), HERE):
            if d not in self.dirs:
                self.dirs.append(d)

    # -- lookups ---------------------------------------------------------
    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.data["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    cfg = json.load(f)
                cfg["name"] = name
                return cfg
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(self.find("traffic", name + ".json")) as f:
            mix = json.load(f)
        mix["name"] = name
        return mix

    def metrics_for(self, cell_name: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports: an
        entry without ``workloads`` belongs to every cell."""
        return [m for m in self.data[group]
                if cell_name in m.get("workloads", [cell_name])]

    # -- files found by name ---------------------------------------------
    def find(self, kind: str, filename: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, kind, filename)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"{kind}/{filename} under none of {self.dirs}")

    def module(self, kind: str, name: str):
        """Import ``<kind>/<name>.py`` by path (metric names hold dots, so
        these are not importable by module name), once: a module that is
        executed twice would trace and look up its jitted functions twice."""
        path = self.find(kind, name + ".py")
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]
