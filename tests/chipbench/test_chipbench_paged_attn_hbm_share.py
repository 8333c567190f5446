"""chipbench/layer_metrics/paged_attn_hbm_share.py on the recorded v5e
trace (my chip run, PR 24: 0.43 s of qwen2-longgen about one fused
decode dispatch of the parent's one-page-a-turn kernel)."""
import json
import shutil

import pytest

from chipbench import costs, hostspans, manifest

RECORDED = manifest.HERE / "testdata" / "v5e_hostspans_slice.xplane.pb"
#: PR 23's recorded trace: device planes only, no scopes, no host planes
BARE = manifest.HERE / "testdata" / "v5e_decode_slice.xplane.pb"
PEAKS = json.loads((manifest.HERE / "peaks.json").read_text())["TPU v5 lite"]


def ctx_for(**over) -> dict:
    """What run.py hands the reader, as far as it looks: two fused
    dispatches of 63 rows inside the slice (one emitted 7.9 tokens a row:
    a row finished), a mixed step and a dispatch outside it."""
    with open(manifest.HERE / "configs" / "qwen2-7b-int8.json") as f:
        hf = json.load(f)
    fused = {"kind": "decode_multi", "n_decode": 63, "tokens": 504}
    ctx = {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [
            {**fused, "ts": 100.1, "active_pages": 700},
            {**fused, "ts": 100.3, "active_pages": 708, "tokens": 498},
            {"kind": "mixed", "ts": 100.2, "n_decode": 63, "n_prefill": 1,
             "tokens": 64, "active_pages": 5000},
            {**fused, "ts": 101.0, "active_pages": 5000},
        ],
        "hf": hf, "weights": {"itemsize": 2, "dense_itemsize": 1},
        "page_size": 64, "kernels": True, "peaks": PEAKS,
    }
    ctx.update(over)
    return ctx


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)
    hostspans._THIS_RUN.clear()

    def place(src):
        d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, d / "host.xplane.pb")
        hostspans._THIS_RUN.clear()

    yield place
    hostspans._THIS_RUN.clear()


def test_share_on_the_recorded_trace(run_dir, capsys):
    run_dir(RECORDED)
    read = manifest.layer_reader("paged_attn_hbm_share")
    value = read(ctx_for())
    # by hand: `attn/paged` holds 134.632 ms of the one dispatch of 8
    # steps; the two records about it hold (700 + 708) / 2 pages of 64
    # less half a page for each of 63 rows
    live = 704 * 64 - 63 * 32
    step_s = 0.134632 / 8
    want = 100.0 * live * 57344 / step_s / 819e9
    assert costs.kv_bytes_per_token(ctx_for()["hf"], 2, True) == 57344
    assert value == pytest.approx(want, rel=1e-4)
    assert 15.0 < value < 20.0  # a fifth of the floor, as PERF.md 5 says


def test_share_follows_the_bytes_and_never_the_weights(run_dir, capsys):
    run_dir(RECORDED)
    read = manifest.layer_reader("paged_attn_hbm_share")
    base = read(ctx_for())
    int8_kv = read(ctx_for(weights={"itemsize": 1, "dense_itemsize": 1}))
    assert int8_kv == pytest.approx(base / 2, rel=1e-9)
    bf16_weights = read(ctx_for(weights={"itemsize": 2, "dense_itemsize": 2}))
    assert bf16_weights == pytest.approx(base, rel=1e-12)


@pytest.mark.parametrize("case", [
    "no_trace", "bare_trace", "cpu_no_peaks", "no_slice", "no_fused_record",
])
def test_share_is_none_not_an_error_when_there_is_nothing_to_read(
        case, run_dir, capsys):
    read = manifest.layer_reader("paged_attn_hbm_share")
    if case == "bare_trace":
        run_dir(BARE)  # the parent of PR 24: no scopes, no engine spans
    elif case != "no_trace":
        run_dir(RECORDED)
    over = {
        "cpu_no_peaks": {"peaks": None},
        "no_slice": {"trace_info": {}},
        "no_fused_record": {"flight": [
            {"kind": "decode", "ts": 100.1, "n_decode": 63, "tokens": 63,
             "active_pages": 700}]},
    }.get(case, {})
    assert read(ctx_for(**over)) is None
