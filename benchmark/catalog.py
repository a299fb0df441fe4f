"""Finds the benchmark's files by the names ``BENCHMARK.json`` gives.

One configuration, traffic mix and cell is one data file; one family,
plain reference, job kind and per-layer metric is one module. Nothing
here knows a name: a later PR adds files and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CatalogError(LookupError):
    """A name the benchmark was given leads to no file, or to a file that
    disagrees with ``BENCHMARK.json``."""


class Catalog:
    """The benchmark as found under ``root`` (a checkout of the repo)."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.index = self._json("BENCHMARK.json")
        self.home = os.path.join(root, self.index["paths"][0])

    def _json(self, *parts):
        path = os.path.join(self.root, *parts)
        try:
            with open(path) as f:
                return json.load(f)
        except FileNotFoundError:
            raise CatalogError(f"no such file: {path}") from None

    def _entry(self, key, name):
        if not NAME_RE.match(name):
            raise CatalogError(f"not a name: {name!r}")
        for entry in self.index[key]:
            if entry["name"] == name:
                return entry
        raise CatalogError(f"BENCHMARK.json lists no {name!r} under {key}")

    def _data(self, kind, name):
        if not NAME_RE.match(name):
            raise CatalogError(f"not a name: {name!r}")
        return self._json(self.home, kind, name + ".json")

    def cell(self, name):
        """The cell's own file merged over its ``BENCHMARK.json`` entry;
        the two must agree where they overlap."""
        entry = self._entry("workloads", name)
        cell = self._data("workloads", name)
        for key in ("config", "traffic", "chips"):
            if cell.get(key) != entry[key]:
                raise CatalogError(
                    f"workloads/{name}.json says {key}={cell.get(key)!r}, "
                    f"BENCHMARK.json says {entry[key]!r}")
        return {**entry, **cell}

    def config(self, name):
        """The configuration as it is run. A cell's configuration is read
        from the ``file`` its ``BENCHMARK.json`` entry names; a rehearsal
        preset has no entry and is found by name alone."""
        for entry in self.index["configs"]:
            if entry["name"] == name:
                return self._json(entry["file"])
        return self._data("configs", name)

    def traffic(self, name):
        return self._data("traffic", name)

    def names(self, cell):
        """The files of kernel and scope names the cell's own file lists
        under ``"names"`` (``hlo_counts.load_names``); most list none."""
        return [self._data("names", name) for name in cell.get("names", ())]

    def module(self, kind, name):
        """``<home>/<kind>/<name>.py`` as a module; names may hold ``-``
        and ``.``, so it is loaded by path."""
        if not NAME_RE.match(name):
            raise CatalogError(f"not a name: {name!r}")
        path = os.path.join(self.home, kind, name + ".py")
        if not os.path.isfile(path):
            raise CatalogError(f"no such {kind} module: {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, key, cell_name):
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.index[key]
                if cell_name in m.get("workloads", [cell_name])]
