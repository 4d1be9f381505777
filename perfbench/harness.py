"""What every job of the benchmark shares: where files live, how a cell is
found from its name, how a configuration file becomes the program's
config, the device check, the compile cache, and the result line.

Data drives it. A cell `<name>` is `perfbench/workloads/<name>.json`, which
names its job kind (`train` or `serve`), its configuration
(`perfbench/configs/<config>.json`) and its traffic mix
(`perfbench/traffic/<traffic>.json`). A per-layer metric `<metric>` is read
by `perfbench/metrics/<metric>.py`, found by the metric's own name. Adding a
cell, a configuration, a mix or a metric adds files and list entries in
BENCHMARK.json and edits nothing here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: Optional[str] = None) -> Dict[str, Any]:
    return load_json(path or MANIFEST)


def load_cell(name: str, base: str = HERE) -> Dict[str, Any]:
    """The cell's own file, with its configuration and traffic mix read in.
    `base` is perfbench/ itself; the tests keep tiny cells under their own."""
    cell = load_json(os.path.join(base, "workloads", name + ".json"))
    cell["name"] = name
    cell["conf"] = load_json(os.path.join(base, "configs",
                                          cell["config"] + ".json"))
    cell["mix"] = load_json(os.path.join(base, "traffic",
                                         cell["traffic"] + ".json"))
    return cell


def program_config(conf: Dict[str, Any], **over) -> Dict[str, Any]:
    """Keyword arguments of the program's TransformerConfig for a
    configuration file (GPT-2 key names, as the source's config.json)."""
    opt = conf.get("optimizer", {})
    kw = dict(
        vocab_size=conf["vocab_size"], d_model=conf["n_embd"],
        n_layers=conf["n_layer"], n_heads=conf["n_head"],
        d_ff=conf["n_inner"], max_len=conf["n_positions"],
        dtype_policy=conf["dtype_policy"],
        learning_rate=opt.get("learning_rate", 3e-4),
        weight_decay=opt.get("weight_decay", 0.0),
        clip_grad_norm=opt.get("clip_grad_norm", 0.0),
    )
    kw.update(over)
    return kw


def cell_metrics(manifest: Dict[str, Any], cell_name: str, group: str,
                 reported: Optional[List[str]] = None) -> List[Dict[str, Any]]:
    """The metrics of `group` (`end_to_end` or `per_layer`) that this cell
    reports: those that list it under `workloads`, and those without the key.
    A per-layer metric without the key belongs to every cell that reports
    the end-to-end metric it moves (`reported`)."""
    out = []
    for m in manifest[group]:
        cells = m.get("workloads")
        if cells is not None:
            if cell_name in cells:
                out.append(m)
        elif group == "end_to_end" or reported is None \
                or m["moves"] in reported:
            out.append(m)
    return out


def load_reader(metric_name: str):
    """`perfbench/metrics/<metric>.py`, which has `read(ctx)`."""
    path = os.path.join(HERE, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(
            f"perfbench: device_kind {device_kind!r} is not in "
            f"perfbench/peaks.json ({sorted(k for k in table if k[0] != '_')}): "
            "no result")
    return table[device_kind]


def set_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment already names one. The program takes the same variable
    (ops/dispatch.compile_cache_dir), so both write to one place."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not d:
        d = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    import jax

    jax.config.update("jax_compilation_cache_dir", d)
    # small programs too: a run's set-up should find every program there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def check_device(chips: int) -> Dict[str, Any]:
    """The devices JAX found, or exit non-zero with no result: an
    accelerator in the peaks table, and as many chips as the cell asks."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform == "cpu":
        raise SystemExit("perfbench: JAX found no accelerator (platform "
                         "'cpu'): no result")
    peaks_for(d0.device_kind)
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell asks for {chips} chips, JAX "
                         f"found {len(devs)}: no result")
    return describe_device(chips)


def describe_device(chips: int) -> Dict[str, Any]:
    import jax

    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak on the fullest of the chips used (0 where the backend does not
    say, as on the CPU of a test)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def trace_dir() -> str:
    """Where a traced run keeps the profiler's file until it is reduced: one
    fixed directory inside the checkout, emptied first."""
    d = os.path.join(ROOT, ".perfbench_trace")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def seed_key(seed: int):
    """A PRNG key for any whole number, also past 32 signed bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    """Each number compared beside its limit, as the last lines on stderr."""
    for name, c in checks.items():
        if c.get("compared") is False:
            limit = "none (not compared)"
        else:
            limit = "missing" if c["limit"] is None else f"{c['limit']:.6g}"
        value = "not finite" if c["value"] is None else f"{c['value']:.6g}"
        say(f"check {name}: value {value} limit {limit} "
            f"{'ok' if c['ok'] else 'FAILED'}")
