"""scripts/mixed_step_breakdown.py (PR 39): where a mixed step's device
time goes, from a trace file. Read here on the benchmark's recorded v5e
trace (0.43 s of qwen2-longgen with two mixed steps in it, whose
`attn/flash` is the GQA kernel's: the script knows scopes, the cell says
whose they are)."""
import importlib.util
import json
from pathlib import Path

import pytest

from chipbench import hostspans, manifest

RECORDED = manifest.HERE / "testdata" / "v5e_hostspans_slice.xplane.pb"
#: device planes only, no scopes
BARE = manifest.HERE / "testdata" / "v5e_decode_slice.xplane.pb"


@pytest.fixture(scope="module")
def script():
    path = Path(__file__).resolve().parent.parent / "scripts" / (
        "mixed_step_breakdown.py")
    spec = importlib.util.spec_from_file_location("mixed_step_breakdown",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_self_time_of_the_scope_over_the_mixed_dispatches(script):
    loaded = hostspans.load(str(RECORDED))
    per_scope = hostspans.scope_self_s(loaded, "jit_mixed_fn")
    value = script.flash_ms_per_mixed_step(loaded)
    # by hand: two mixed steps hold 2.665 ms under `attn/flash`, and
    # 32.8 ms under `attn/paged` that this reading must not count
    assert per_scope["_count"] == 2
    assert value == pytest.approx(1.3325, abs=5e-4)
    assert 1e3 * per_scope["attn/paged"] / 2 > 10 * value


@pytest.mark.parametrize("case", ["bare_trace", "no_such_scope",
                                  "no_mixed_step"])
def test_none_not_an_error_when_there_is_nothing_to_read(
        case, script, monkeypatch):
    loaded = hostspans.load(str(BARE if case == "bare_trace" else RECORDED))
    if case == "no_such_scope":
        real = hostspans.scope_self_s
        monkeypatch.setattr(hostspans, "scope_self_s", lambda *a: {
            k: v for k, v in real(*a).items() if k != "attn/flash"})
    elif case == "no_mixed_step":
        loaded = {"spans": loaded["spans"], "devices": {
            name: {"ops": dev["ops"], "modules": [
                m for m in dev["modules"] if m[0] != "jit_mixed_fn"]}
            for name, dev in loaded["devices"].items()}}
    assert script.flash_ms_per_mixed_step(loaded) is None


def test_the_lines_it_prints_for_a_trace_file(script, capsys):
    assert script.main([str(RECORDED)]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    mixed = lines[0]
    assert mixed["module"] == "jit_mixed_fn" and mixed["dispatches"] == 2
    assert mixed["self_ms_by_scope"]["attn/flash"] == pytest.approx(
        1.3325, abs=5e-4)
    assert lines[1]["module"] == "jit_multi_fn"
    assert lines[-1] == {"latent_flash_ms_per_mixed_step": pytest.approx(
        1.3325, abs=5e-4)}


def test_no_trace_under_the_run_directory_is_exit_code_2(
        script, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)
    assert script.main([]) == 2
    assert "no trace" in capsys.readouterr().err
