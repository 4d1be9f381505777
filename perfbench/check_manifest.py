#!/usr/bin/env python3
"""Validate BENCHMARK.json before anything runs.

    python3 perfbench/check_manifest.py [path/to/BENCHMARK.json]

Exits non-zero, naming each fault, unless the manifest keeps the rules a
PR was once lost to (PR 22: a layer named with a space): every name of a
metric, cell, configuration, traffic mix and LAYER is one token; units have
no space; every `moves` names an end-to-end metric that every cell reporting
the per-layer metric also reports; bounds are shares, one per metric, none per
cell and none absolute; and the files each cell needs exist. It runs here on
the CPU and again as the first step of every run.
"""
from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
# a width may never be reduced
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|head_dim|expansion|per_tok|n_embd|n_inner|"
                   r"d_model|d_ff)")


def _line(s: Any, lo: int = 1, hi: int = 200) -> bool:
    return (isinstance(s, str) and lo <= len(s) <= hi
            and "\n" not in s and "\t" not in s and "\r" not in s)


def check(manifest: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Every fault found, as text; empty when the manifest is sound."""
    bad: List[str] = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}, got "
                   f"{sorted(manifest)}")
        return bad

    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH.match(p)
                    and not p.startswith("/") and ".." not in p.split("/")
                    for p in paths)):
        bad.append(f"paths must be 1 to 16 relative directories: {paths!r}")
        return bad
    under = lambda f: any(f == p or f.startswith(p.rstrip("/") + "/")
                          for p in paths)
    cmd = manifest["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        bad.append("command must be a list of 1 to 32 one-line strings")
    else:
        for w in cmd:
            if w.startswith("/") or ".." in w.split("/"):
                bad.append(f"command word {w!r} leaves the repo")
            elif "/" in w and not under(w):
                bad.append(f"command names {w!r}, which is outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= 51):
        bad.append(f"run_seconds must be a whole number from 1 to 51: {rs!r}")

    def entries(group: str, lo: int, hi: int) -> List[Dict[str, Any]]:
        rows = manifest[group]
        if not (isinstance(rows, list) and lo <= len(rows) <= hi
                and all(isinstance(r, dict) for r in rows)):
            bad.append(f"{group} must be a list of {lo} to {hi} objects")
            return []
        allowed = KEYS[group]
        for r in rows:
            extra = set(r) - allowed - ({"workloads"} if group in
                                        ("end_to_end", "per_layer") else set())
            missing = allowed - set(r)
            if extra or missing:
                bad.append(f"{group} {r.get('name')!r}: keys not allowed "
                           f"{sorted(extra)}, keys missing {sorted(missing)}")
            if not (isinstance(r.get("name"), str) and NAME.match(r["name"])):
                bad.append(f"{group} name {r.get('name')!r} must be 1 to 64 "
                           "letters, digits, '_', '.' and '-', starting with "
                           "a letter, digit or '_'")
        names = [r.get("name") for r in rows]
        for n in set(names):
            if names.count(n) > 1:
                bad.append(f"{group}: the name {n!r} appears twice")
        return rows

    configs = entries("configs", 1, 24)
    cells = entries("workloads", 1, 24)
    e2e = entries("end_to_end", 1, 16)
    layer_metrics = entries("per_layer", 1, 128)
    if bad:
        return bad
    both = [m["name"] for m in e2e] + [m["name"] for m in layer_metrics]
    for n in set(both):
        if both.count(n) > 1:
            bad.append(f"the metric name {n!r} appears twice")

    files = []
    for c in configs:
        if not _line(c["source"]) or not _line(c["why"]):
            bad.append(f"config {c['name']}: source and why are one line of "
                       "1 to 200 characters")
        f = c["file"]
        if not (isinstance(f, str) and PATH.match(f) and under(f)):
            bad.append(f"config {c['name']}: file {f!r} must lie under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            bad.append(f"config {c['name']}: file {f} does not exist")
        else:
            try:
                with open(os.path.join(root, f)) as fh:
                    body = json.load(fh)
            except ValueError as e:
                bad.append(f"config {c['name']}: {f} is not JSON: {e}")
                body = {}
            for k in c["reduced"] if isinstance(c["reduced"], list) else []:
                if k not in body:
                    bad.append(f"config {c['name']}: reduced key {k!r} is "
                               f"not in {f}")
        files.append(f)
        red = c["reduced"]
        if not (isinstance(red, list) and len(red) <= 16
                and all(isinstance(k, str) and NAME.match(k) for k in red)):
            bad.append(f"config {c['name']}: reduced must be a list of at "
                       "most 16 key names")
        else:
            for k in red:
                if WIDTH.search(k):
                    bad.append(f"config {c['name']}: reduced names the "
                               f"width {k!r}")
    for f in set(files):
        if files.count(f) > 1:
            bad.append(f"two configurations share the file {f}")

    config_names = {c["name"] for c in configs}
    pairs = []
    for w in cells:
        if w["config"] not in config_names:
            bad.append(f"cell {w['name']}: no configuration {w['config']!r}")
        if not (isinstance(w["traffic"], str) and NAME.match(w["traffic"])):
            bad.append(f"cell {w['name']}: traffic {w['traffic']!r} is not "
                       "a name")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips must be 1 or 4")
        if not _line(w["why"]):
            bad.append(f"cell {w['name']}: why is one line of 1 to 200 "
                       f"characters, got {len(str(w['why']))}")
        pairs.append((w["config"], w["traffic"]))
        cell_file = os.path.join(root, paths[0], "workloads",
                                 w["name"] + ".json")
        if not os.path.isfile(cell_file):
            bad.append(f"cell {w['name']}: {os.path.relpath(cell_file, root)} "
                       "does not exist")
            continue
        with open(cell_file) as fh:
            body = json.load(fh)
        for key in ("config", "traffic", "chips"):
            if body.get(key) != w[key]:
                bad.append(f"cell {w['name']}: its file says {key} "
                           f"{body.get(key)!r}, the manifest {w[key]!r}")
        for kind, nm in (("configs", w["config"]), ("traffic", w["traffic"])):
            p = os.path.join(root, paths[0], kind, str(nm) + ".json")
            if not os.path.isfile(p):
                bad.append(f"cell {w['name']}: {os.path.relpath(p, root)} "
                           "does not exist")
    for p in set(pairs):
        if pairs.count(p) > 1:
            bad.append(f"the pair {p} of configuration and traffic appears "
                       "twice")
    for c in config_names - {w["config"] for w in cells}:
        bad.append(f"configuration {c} is used by no cell")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} cells ask for 4 chips, more than a quarter")

    cell_names = {w["name"] for w in cells}

    def metric_common(m: Dict[str, Any], group: str) -> None:
        if not (isinstance(m["unit"], str) and UNIT.match(m["unit"])):
            bad.append(f"{group} metric {m['name']}: unit {m['unit']!r} must "
                       "be 1 to 16 of letters, digits, '_', '/', '%', '.', "
                       "'-', with no space")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{group} metric {m['name']}: better is lower or "
                       "higher")
        if m["source"] not in SOURCES:
            bad.append(f"{group} metric {m['name']}: source {m['source']!r} "
                       f"is none of {SOURCES}")
        if "workloads" in m:
            ws = m["workloads"]
            if not (isinstance(ws, list) and ws
                    and all(x in cell_names for x in ws)):
                bad.append(f"{group} metric {m['name']}: workloads {ws!r} "
                           "must list registered cells")

    def cells_of(m: Dict[str, Any]) -> set:
        return set(m["workloads"]) if "workloads" in m else set(cell_names)

    for m in e2e:
        metric_common(m, "end_to_end")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end metric {m['name']}: source must be "
                       "host_clock or device_trace")
        b = m["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.1):
            bad.append(f"end_to_end metric {m['name']}: bound {b!r} must be "
                       "one share from 0.01 to 0.1 (none per cell, none "
                       "absolute)")
    e2e_by_name = {m["name"]: m for m in e2e}
    if "setup_s" not in e2e_by_name:
        bad.append("end_to_end must hold setup_s")
    elif "workloads" in e2e_by_name["setup_s"]:
        bad.append("setup_s is reported by every cell: no workloads key")

    for m in layer_metrics:
        metric_common(m, "per_layer")
        if not (isinstance(m["layer"], str) and NAME.match(m["layer"])):
            bad.append(f"per_layer metric {m['name']}: layer "
                       f"{m['layer']!r} must be 1 to 64 characters from "
                       "letters, digits, '_', '.' and '-', starting with a "
                       "letter, digit or '_'")
        target = e2e_by_name.get(m["moves"])
        if target is None:
            bad.append(f"per_layer metric {m['name']}: moves "
                       f"{m['moves']!r} is no end-to-end metric")
            continue
        if "workloads" in m:
            lost = set(m["workloads"]) - cells_of(target)
            if lost:
                bad.append(f"per_layer metric {m['name']}: cells "
                           f"{sorted(lost)} do not report {m['moves']}")
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] \
                or "mfu" in m["name"]:
            if m["unit"] != "%":
                bad.append(f"per_layer metric {m['name']}: a share of a "
                           "roofline or of a peak has the unit %")

    for w in cells:
        mine = [m for m in e2e if w["name"] in cells_of(m)]
        if len([m for m in mine if m["name"] != "setup_s"]) < 1:
            bad.append(f"cell {w['name']} reports no end-to-end metric "
                       "besides setup_s")
        mine_names = {m["name"] for m in mine}
        layer_mine = [m for m in layer_metrics
                      if (w["name"] in m["workloads"] if "workloads" in m
                          else m["moves"] in mine_names)]
        if not layer_mine:
            bad.append(f"cell {w['name']} reports no per-layer metric")
        for m in layer_mine:
            reader = os.path.join(root, paths[0], "metrics",
                                  m["name"] + ".py")
            if not os.path.isfile(reader):
                bad.append(f"per_layer metric {m['name']}: no reader "
                           f"{os.path.relpath(reader, root)}")
    return list(dict.fromkeys(bad))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else os.path.join(ROOT, "BENCHMARK.json")
    try:
        with open(path) as f:
            raw = f.read()
        manifest = json.loads(raw)
    except (OSError, ValueError) as e:
        print(f"check_manifest: cannot read {path}: {e}", file=sys.stderr)
        return 1
    bad = check(manifest, os.path.dirname(os.path.abspath(path)))
    if len(raw.encode()) > 64 * 1024:
        bad.append("the manifest is larger than 64 KiB")
    for b in bad:
        print("check_manifest: " + b, file=sys.stderr)
    if not bad:
        print(f"check_manifest: {path} is sound "
              f"({len(manifest['workloads'])} cells)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
