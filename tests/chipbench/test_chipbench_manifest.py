"""BENCHMARK.json against the contract's limits and the files it names;
and that a cell, a model and a per-layer metric are added by new files
plus new entries alone."""
import json
import re

import pytest

from chipbench import manifest, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_dim",
               "expansion", "experts_per")


@pytest.fixture(scope="module")
def man():
    return manifest.load()


def one_line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(man)) < 64 * 1024
    assert 1 <= len(man["command"]) <= 32
    assert all(one_line(w) for w in man["command"])
    assert 1 <= len(man["paths"]) <= 16
    for p in man["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (manifest.ROOT / p).is_dir()
    assert isinstance(man["run_seconds"], int)
    assert 1 <= man["run_seconds"] <= 51
    # a full check with all 24 cells must fit: (2 + 14 * 24) runs
    runs = 2 + 14 * 24
    assert runs * (man["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(man):
    assert 1 <= len(man["configs"]) <= 24
    names = [c["name"] for c in man["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        with open(manifest.ROOT / c["file"]) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not any(w in key for w in WIDTH_WORDS)
            assert not key.endswith(("_dim", "_rank"))
            assert key in conf["assumed"], "every cut states its reason"
        for key in ("preset", "serve_flags", "deployment", "rehearsal",
                    "reference_tolerance", "hidden_size", "vocab_size"):
            assert key in conf


def test_a_configurations_own_modules_are_files_under_paths(man):
    """`reference_module` / `costs_module`: optional, and where named a
    file under `paths` that exports what the harness calls."""
    from chipbench import costs, reference

    for c in man["configs"]:
        with open(manifest.ROOT / c["file"]) as f:
            conf = json.load(f)
        for key, default, fn in (("reference_module", reference, "compare"),
                                 ("costs_module", costs, "step_read_bytes")):
            if key in conf:
                assert PATH.match(conf[key])
                assert any(conf[key].startswith(p + "/")
                           for p in man["paths"])
            assert callable(getattr(manifest.module_of(conf, key, default),
                                    fn))


def test_workloads(man):
    ws = man["workloads"]
    assert 1 <= len(ws) <= 24
    assert len({w["name"] for w in ws}) == len(ws)
    assert len({(w["config"], w["traffic"]) for w in ws}) == len(ws)
    assert sum(w["chips"] == 4 for w in ws) <= max(1, len(ws) // 4)
    for w in ws:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and one_line(w["why"])
        mix = manifest.traffic_of(w)
        assert mix["loop"] == "closed" and mix["why"]
        assert {"ramp_tokens", "ramp_lead_s", "rehearsal",
                "shape_seed"} <= set(mix)
        manifest.config_of(man, w)


def test_metrics(man):
    cells = {w["name"] for w in man["workloads"]}
    e2e, layers = man["end_to_end"], man["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e_cells = {m["name"]: set(m.get("workloads", cells)) for m in e2e}
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"]) and m["source"] in SOURCES
        assert (manifest.HERE / "layer_metrics" / f"{m['name']}.py").exists()
        assert callable(manifest.layer_reader(m["name"]))
        # the metric it should move is reported wherever it is
        assert set(m.get("workloads", cells)) <= e2e_cells[m["moves"]]
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for c in cells:
        mine = [m["name"] for m in manifest.metrics_of(man, "end_to_end", c)]
        assert "setup_s" in mine and len(mine) >= 2
        assert manifest.metrics_of(man, "per_layer", c)


def test_a_tail_too_unsteady_for_a_bound_is_per_layer_in_that_cell(man):
    """`itl_p95_ms` is end to end only where its runs repeat inside
    half of the largest bound (PERF.md 6, PR 26); `qwen2-longgen` reads
    the same gaps per layer, under another name."""
    def names(kind, cell):
        return [m["name"] for m in manifest.metrics_of(man, kind, cell)]

    assert names("end_to_end", "qwen2-longgen") == ["output_tok_s", "setup_s"]
    assert "itl_p95_ms" in names("end_to_end", "phi3-chat-closed")
    assert "itl_p95_ms.longgen" in names("per_layer", "qwen2-longgen")
    assert "itl_p95_ms.longgen" not in names("per_layer", "phi3-chat-closed")
    read = manifest.layer_reader("itl_p95_ms.longgen")
    gaps = [float(g) for g in range(1, 102)]
    assert read({"client": {"gaps_ms": gaps}}) == 96.0
    assert read({"client": {"gaps_ms": []}}) is None


def test_files_under_paths_use_only_the_allowed_characters(man):
    for p in man["paths"]:
        for f in (manifest.ROOT / p).rglob("*"):
            rel = str(f.relative_to(manifest.ROOT))
            if "__pycache__" in rel:
                continue
            assert PATH.match(rel), rel


def test_a_cell_a_model_and_a_metric_are_added_by_files_and_entries(
        man, tmp_path):
    """A later PR edits no file that is there: it drops a configuration
    file, a traffic file and a reader beside the others and names them
    in BENCHMARK.json."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "configs").mkdir()
    conf = {"source": "https://example.org/dummy", "preset": "tiny",
            "serve_flags": [], "reduced": [], "vocab_size": 256}
    (tmp_path / "configs" / "dummy-model.json").write_text(json.dumps(conf))
    mix = {"why": "dummy", "loop": "closed", "clients": 3,
           "requests_per_client": 2, "ramp_tokens": 10, "ramp_lead_s": 1.0,
           "shape_seed": 1,
           "prompt_tokens": {"dist": "const", "value": 8},
           "output_tokens": {"dist": "uniform_int", "min": 4, "max": 6}}
    (tmp_path / "traffic" / "dummy-short.json").write_text(json.dumps(mix))
    (tmp_path / "layer_metrics" / "dummy_steps.py").write_text(
        'def read(ctx):\n    return float(len(ctx["flight"])) or None\n')
    grown = json.loads(json.dumps(man))
    grown["configs"].append({
        "name": "dummy-model", "source": conf["source"], "reduced": [],
        "file": str(tmp_path / "configs" / "dummy-model.json"), "why": "x"})
    grown["workloads"].append({
        "name": "dummy-cell", "config": "dummy-model",
        "traffic": "dummy-short", "chips": 1, "why": "x"})
    grown["per_layer"].append({
        "name": "dummy_steps", "unit": "steps", "better": "lower",
        "source": "program_counter", "layer": "engine loop (engine/engine.py)",
        "moves": "output_tok_s", "workloads": ["dummy-cell"]})
    cell = manifest.cell(grown, "dummy-cell")
    assert manifest.config_of(grown, cell)["preset"] == "tiny"
    got = manifest.traffic_of(cell, base=tmp_path)
    plan = traffic.plan(got, 1, 256)
    assert [len(c) for c in plan.clients] == [2, 2, 2]
    assert all(len(t.new_ids) == 8 for c in plan.clients for t in c)
    per_layer = [m["name"] for m in
                 manifest.metrics_of(grown, "per_layer", "dummy-cell")]
    assert "dummy_steps" in per_layer and "decode_hbm_share" in per_layer
    read = manifest.layer_reader("dummy_steps", base=tmp_path)
    assert read({"flight": [1, 2, 3]}) == 3.0
    assert read({"flight": []}) is None  # nothing to read: left out
    # a metric with a `workloads` key stays in its cells: the new cell
    # reports no `itl_p95_ms` and none of another cell's split readings
    assert [m["name"] for m in
            manifest.metrics_of(grown, "end_to_end", "dummy-cell")] == \
        ["output_tok_s", "setup_s"]
    assert "itl_p95_ms.longgen" not in per_layer
    # the cells that were there report what they reported
    assert [m["name"] for m in
            manifest.metrics_of(grown, "per_layer", "qwen2-longgen")] == \
        [m["name"] for m in manifest.metrics_of(man, "per_layer", "qwen2-longgen")]
