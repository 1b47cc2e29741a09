"""Files found by name. A cell names its configuration and traffic mix; the
configuration names its generator, the traffic mix its loop kind and query
classes, and each per-layer metric has a reader under its own name. Each is
`<kind>/<name>.<ext>` in one of the benchmark's directories, so a later PR
adds a file and edits none. An unknown name is an error that names it."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import List, Optional

DATA_EXTS = (".json",)
CODE_EXTS = (".py",)


class UnknownName(Exception):
    pass


class Registry:
    def __init__(self, dirs: List[str]):
        self.dirs = list(dirs)
        self._modules = {}

    def find(self, kind: str, name: str, exts=DATA_EXTS + CODE_EXTS) -> Optional[str]:
        for d in self.dirs:
            for ext in exts:
                path = os.path.join(d, kind, name + ext)
                if os.path.isfile(path):
                    return path
        return None

    def _need(self, kind: str, name: str, exts) -> str:
        path = self.find(kind, name, exts)
        if path is None:
            looked = ", ".join(os.path.join(d, kind) for d in self.dirs)
            raise UnknownName(
                f"no {kind} file named {name!r} ({'/'.join(exts)}) in {looked}")
        return path

    def data(self, kind: str, name: str) -> dict:
        with open(self._need(kind, name, DATA_EXTS)) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        path = self._need(kind, name, CODE_EXTS)
        if path not in self._modules:
            modname = f"_bench_{kind}_{name}".replace("-", "_").replace(".", "_")
            spec = importlib.util.spec_from_file_location(modname, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[modname] = mod
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def reader(self, metric: str):
        """The reader of one per-layer metric: ``readers/<metric>.py`` with
        ``read(ctx)``, or ``readers/<metric>.json`` naming a shared reader
        module and the parameters it is called with."""
        path = self._need("readers", metric, CODE_EXTS + DATA_EXTS)
        if path.endswith(".py"):
            return self.module("readers", metric).read
        with open(path) as f:
            entry = json.load(f)
        read = self.module("readers", entry["reader"]).read
        params = entry.get("params", {})
        return lambda ctx: read(ctx, **params)
