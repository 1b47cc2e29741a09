"""`BENCHMARK.json`: reading it, finding a cell, and the rules a manifest has
to keep (the same rules the driver refuses a file over, so that a tier-1 test
can say so first)."""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
END_TO_END_KEYS = {"name", "unit", "better", "bound", "source"}
PER_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
MAX_BYTES = 64 * 1024


class ManifestError(Exception):
    pass


class Manifest:
    """A parsed `BENCHMARK.json` and the directory it sits in. ``paths`` are
    the benchmark's directories, absolute: files of a cell are looked for in
    each, in order."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        with open(self.path, "rb") as f:
            raw = f.read()
        self.nbytes = len(raw)
        self.data = json.loads(raw)
        self.paths = [os.path.join(self.root, p) for p in self.data["paths"]]

    def cell(self, name: str) -> dict:
        return _by_name(self.data["workloads"], name, "workload", self.path)

    def config(self, name: str) -> dict:
        return _by_name(self.data["configs"], name, "configuration", self.path)

    def config_file(self, name: str) -> str:
        return os.path.join(self.root, self.config(name)["file"])

    def metrics_for(self, kind: str, cell: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports:
        those with no ``workloads`` key, and those that list it."""
        return [m for m in self.data[kind]
                if "workloads" not in m or cell in m["workloads"]]


def _by_name(entries: List[dict], name: str, what: str, where: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    known = ", ".join(e["name"] for e in entries)
    raise ManifestError(f"no {what} named {name!r} in {where} (it has: {known})")


def _one_line(text, what: str, problems: List[str]):
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        problems.append(f"{what}: not 1 to 200 characters on one line")


def problems(m: Manifest, find_file=None) -> List[str]:
    """Every rule of the contract this manifest breaks, as sentences; empty
    when it keeps them all. ``find_file(kind, name)`` returns the path of a
    named file or None, and is asked for each file a cell needs."""
    d, out = m.data, []
    if m.nbytes > MAX_BYTES:
        out.append(f"{m.nbytes} bytes, over {MAX_BYTES}")
    if set(d) != TOP_KEYS:
        out.append(f"top-level keys {sorted(d)} are not {sorted(TOP_KEYS)}")
        return out
    if not (isinstance(d["command"], list) and 1 <= len(d["command"]) <= 32):
        out.append("command: not a list of 1 to 32 strings")
    for word in d["command"]:
        _one_line(word, f"command word {word!r}", out)
        if word.startswith("/") or ".." in word.split("/"):
            out.append(f"command word {word!r} leaves the repo")
    if not 1 <= len(d["paths"]) <= 16:
        out.append("paths: not 1 to 16 directories")
    for p in d["paths"]:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            out.append(f"path {p!r}: not a relative path of allowed characters")
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        out.append("run_seconds: not a whole number from 1 to 51")

    def under_paths(rel: str) -> bool:
        return any(rel == p or rel.startswith(p.rstrip("/") + "/")
                   for p in d["paths"])

    def names(entries, what) -> List[str]:
        got = [e.get("name") for e in entries]
        for n in got:
            if not isinstance(n, str) or not NAME_RE.match(n):
                out.append(f"{what} name {n!r}: not a name")
        for n in sorted({n for n in got if got.count(n) > 1}, key=str):
            out.append(f"{what} name {n!r} appears more than once")
        return got

    if not 1 <= len(d["configs"]) <= 24:
        out.append("configs: not 1 to 24")
    config_names = names(d["configs"], "config")
    files = []
    for c in d["configs"]:
        if set(c) != CONFIG_KEYS:
            out.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        _one_line(c["source"], f"config {c['name']} source", out)
        _one_line(c["why"], f"config {c['name']} why", out)
        if not PATH_RE.match(c["file"]) or not under_paths(c["file"]):
            out.append(f"config {c['name']}: file {c['file']!r} not under paths")
        elif not os.path.isfile(os.path.join(m.root, c["file"])):
            out.append(f"config {c['name']}: file {c['file']!r} does not exist")
        files.append(c["file"])
        if len(c["reduced"]) > 16 or not all(
                isinstance(k, str) and NAME_RE.match(k) for k in c["reduced"]):
            out.append(f"config {c['name']}: reduced is not at most 16 names")
    if len(set(files)) != len(files):
        out.append("two configurations share a file")

    if not 2 <= len(d["workloads"]) <= 24:
        out.append("workloads: not 2 to 24")
    cell_names = names(d["workloads"], "workload")
    pairs = []
    for w in d["workloads"]:
        if set(w) != WORKLOAD_KEYS:
            out.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        _one_line(w["why"], f"workload {w['name']} why", out)
        if w["config"] not in config_names:
            out.append(f"workload {w['name']}: no config {w['config']!r}")
        if not NAME_RE.match(w["traffic"]):
            out.append(f"workload {w['name']}: traffic {w['traffic']!r} not a name")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']!r} not 1 or 4")
        pairs.append((w["config"], w["traffic"]))
    if len(set(pairs)) != len(pairs):
        out.append("a pair of configuration and traffic appears twice")
    for c in config_names:
        if c not in {w.get("config") for w in d["workloads"]}:
            out.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in d["workloads"] if w.get("chips") == 4)
    if four > max(1, len(d["workloads"]) // 2):
        out.append(f"{four} of {len(d['workloads'])} cells ask for four chips")

    if not 1 <= len(d["end_to_end"]) <= 16:
        out.append("end_to_end: not 1 to 16")
    if not 1 <= len(d["per_layer"]) <= 128:
        out.append("per_layer: not 1 to 128")
    names(d["end_to_end"] + d["per_layer"], "metric")
    e2e = {e.get("name"): e for e in d["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("end_to_end has no setup_s")
    for kind, keys in (("end_to_end", END_TO_END_KEYS),
                       ("per_layer", PER_LAYER_KEYS)):
        for e in d[kind]:
            if set(e) - {"workloads"} != keys:
                out.append(f"{kind} {e.get('name')}: keys {sorted(e)}")
                continue
            if not UNIT_RE.match(e["unit"]):
                out.append(f"metric {e['name']}: unit {e['unit']!r}")
            if e["better"] not in ("lower", "higher"):
                out.append(f"metric {e['name']}: better {e['better']!r}")
            if e["source"] not in SOURCES:
                out.append(f"metric {e['name']}: source {e['source']!r}")
            for w in e.get("workloads", ()):
                if w not in cell_names:
                    out.append(f"metric {e['name']}: no workload {w!r}")
            if kind == "end_to_end":
                if e["source"] not in ("host_clock", "device_trace"):
                    out.append(f"metric {e['name']}: an end-to-end metric is "
                               f"taken from {e['source']}")
                if not 0.01 <= e["bound"] <= 0.25:
                    out.append(f"metric {e['name']}: bound {e['bound']}")
            else:
                _one_line(e["layer"], f"metric {e['name']} layer", out)
                if e["moves"] not in e2e:
                    out.append(f"metric {e['name']}: moves {e['moves']!r}, "
                               "which is no end-to-end metric")
    for cell in cell_names:
        reported = {e["name"] for e in m.metrics_for("end_to_end", cell)}
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"cell {cell}: reports {sorted(reported)} end to end")
        layer = m.metrics_for("per_layer", cell)
        if not layer:
            out.append(f"cell {cell}: reports no per-layer metric")
        for e in layer:
            if e.get("moves") not in reported:
                out.append(f"cell {cell}: {e['name']} moves {e.get('moves')!r}, "
                           "which the cell does not report")
    if find_file is not None:
        for w in d["workloads"]:
            if find_file("traffic", w["traffic"]) is None:
                out.append(f"cell {w['name']}: no traffic file {w['traffic']!r}")
        for e in d["per_layer"]:
            if find_file("readers", e["name"]) is None:
                out.append(f"metric {e['name']}: no reader file")
    return out
