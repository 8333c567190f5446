"""BENCHMARK.json and the data files it names. Everything that belongs
to one configuration, one traffic mix or one per-layer metric lives in a
file of its own, found here by name; nothing in the harness lists them."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
#: everything a run generates (tokenizers, traces) lands here; the
#: compile cache keeps the program's own fixed place (<checkout>/.jax_cache)
RUN_DIR = ROOT / ".chipbench_run"


def load(path: Path | None = None) -> dict:
    with open(path or ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r} in BENCHMARK.json; there are "
        f"{[w['name'] for w in manifest['workloads']]}"
    )


def config_of(manifest: dict, cell_: dict) -> dict:
    for c in manifest["configs"]:
        if c["name"] == cell_["config"]:
            with open(ROOT / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no config {cell_['config']!r} in BENCHMARK.json")


def module_of(conf: dict, key: str, default):
    """What a configuration brings of its own, by file: `conf[key]` is the
    path of a module (from the checkout's root, as a configuration's
    `file` in BENCHMARK.json), or absent, which means `default`.

    `reference_module` exports `compare(params, hf, streams) -> dict`
    as chipbench/reference.py (the default) does, and may export
    `served_widths(cfg) -> dict` keyed by the configuration file's own
    keys. `costs_module` exports `step_read_bytes` and `kv_read_bytes`
    as chipbench/costs.py (the default) does; either may be missing or
    return None, and the metric that asks is then left out of the cell."""
    path = conf.get(key)
    if not path:
        return default
    return _load(ROOT / path, f"chipbench_{key}_{Path(path).stem}")


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_of(cell_: dict, base: Path = HERE) -> dict:
    with open(base / "traffic" / f"{cell_['traffic']}.json") as f:
        return json.load(f)


def metrics_of(manifest: dict, kind: str, cell_name: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics this cell reports: those
    with no `workloads` key, or with the cell in it."""
    return [
        m for m in manifest[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def layer_reader(name: str, base: Path = HERE):
    """The reader of one per-layer metric: `read(ctx) -> float | None`
    from chipbench/layer_metrics/<name>.py."""
    return _load(base / "layer_metrics" / f"{name}.py",
                 f"chipbench_layer_metric_{name}").read
