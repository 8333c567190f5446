"""Perf-regression ledger (ISSUE 19): schema round-trip through
dynamo_tpu/telemetry/perf_ledger.py, the driver-round back-fill
(every recorded round must parse into a valid row), and the
scripts/perf_diff.py CI contract (exit 0 clean / 1 data error / 2
regression)."""

import importlib.util
import json
import pathlib

import pytest

from dynamo_tpu.telemetry import perf_ledger

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_perf_diff():
    spec = importlib.util.spec_from_file_location(
        "perf_diff", REPO / "scripts" / "perf_diff.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- schema ----------------------------------------------------------------


def test_schema_round_trip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    row = perf_ledger.make_row(
        "r42", "bench", {"tok_s": 651.55, "p50_ttft_s": 0.028},
        {"model": "tiny", "isl": 64}, platform="cpu",
    )
    perf_ledger.append_row(row, path)
    rows, problems = perf_ledger.read_rows(path, strict=True)
    assert problems == []
    assert rows == [row]
    assert rows[0]["schema"] == perf_ledger.SCHEMA_VERSION
    assert rows[0]["fingerprint"] == perf_ledger.config_fingerprint(
        {"model": "tiny", "isl": 64}
    )


def test_make_row_drops_unbandable_metrics():
    row = perf_ledger.make_row(
        "r1", "bench",
        {"tok_s": 100.0, "mfu": None, "bad": float("nan"), "flag": True},
        {},
    )
    assert set(row["metrics"]) == {"tok_s"}


def test_validate_row_failures():
    good = perf_ledger.make_row("r1", "bench", {"tok_s": 1.0}, {"m": 1})
    assert perf_ledger.validate_row(good) == []

    missing = {k: v for k, v in good.items() if k != "round"}
    assert any("round" in e for e in perf_ledger.validate_row(missing))

    stale = dict(good, schema=99)
    assert any("schema" in e for e in perf_ledger.validate_row(stale))

    bad_metric = dict(good, metrics={"tok_s": "fast"})
    assert any(
        "not a number" in e for e in perf_ledger.validate_row(bad_metric)
    )

    # a tampered config must not keep the old fingerprint
    forged = dict(good, config={"m": 2})
    assert any(
        "fingerprint" in e for e in perf_ledger.validate_row(forged)
    )


def test_append_row_rejects_invalid(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with pytest.raises(ValueError):
        perf_ledger.append_row({"round": "r1"}, path)
    assert not (tmp_path / "ledger.jsonl").exists()


def test_read_rows_tolerant_of_bad_lines(tmp_path):
    path = tmp_path / "ledger.jsonl"
    good = perf_ledger.make_row("r1", "bench", {"tok_s": 1.0}, {})
    path.write_text(
        json.dumps(good) + "\n"
        + "{not json\n"
        + json.dumps({"round": "r2"}) + "\n"
    )
    rows, problems = perf_ledger.read_rows(str(path))
    assert [r["round"] for r in rows] == ["r1"]
    assert len(problems) == 2
    with pytest.raises(ValueError):
        perf_ledger.read_rows(str(path), strict=True)


def test_rows_by_round_last_wins(tmp_path):
    a = perf_ledger.make_row("r1", "bench", {"tok_s": 1.0}, {})
    b = perf_ledger.make_row("r1", "bench", {"tok_s": 2.0}, {})
    by = perf_ledger.rows_by_round([a, b])
    assert by["r1"]["metrics"]["tok_s"] == 2.0


# -- comparison ------------------------------------------------------------


def _row(name, metrics, config=None, ok=True):
    return perf_ledger.make_row(
        name, "bench", metrics, config if config is not None else {"m": 1},
        ok=ok,
    )


def test_compare_rows_verdicts():
    res = perf_ledger.compare_rows(
        _row("a", {"tok_s": 600.0, "p50_ttft_s": 0.030}),
        _row("b", {"tok_s": 540.0, "p50_ttft_s": 0.029}),
    )
    assert res["comparable"] and not res["advisory"]
    # tok_s -10% past the 8% band; ttft -3.3% inside its 15% band
    assert res["regressions"] == ["tok_s"]
    verdicts = {r["metric"]: r["verdict"] for r in res["rows"]}
    assert verdicts["tok_s"] == "REGRESSION"
    assert verdicts["p50_ttft_s"] == "ok"

    # the same move the other way is an improvement, never flagged
    res = perf_ledger.compare_rows(
        _row("a", {"tok_s": 540.0}), _row("b", {"tok_s": 600.0})
    )
    assert res["regressions"] == []
    assert res["rows"][0]["verdict"] == "improved"


def test_compare_rows_direction_lower_is_better():
    res = perf_ledger.compare_rows(
        _row("a", {"ms_per_dispatch": 10.0}),
        _row("b", {"ms_per_dispatch": 13.0}),
    )
    assert res["regressions"] == ["ms_per_dispatch"]


def test_compare_rows_fingerprint_mismatch_is_advisory():
    res = perf_ledger.compare_rows(
        _row("a", {"tok_s": 600.0}, {"platform": "tpu"}),
        _row("b", {"tok_s": 100.0}, {"platform": "cpu"}),
    )
    assert res["advisory"]
    assert res["regressions"] == []  # different workloads can't regress
    assert "fingerprints differ" in res["note"]


def test_compare_rows_failed_round_not_comparable():
    res = perf_ledger.compare_rows(
        _row("a", {}, ok=False), _row("b", {"tok_s": 1.0})
    )
    assert not res["comparable"]
    assert "failed" in res["note"]


def test_compare_rows_one_sided_metrics_never_verdicted():
    res = perf_ledger.compare_rows(
        _row("a", {"tok_s": 1.0, "mfu": 0.2}), _row("b", {"tok_s": 1.0})
    )
    only = [r for r in res["rows"] if r["metric"] == "mfu"]
    assert only and only[0]["verdict"] == "only in a"
    assert res["regressions"] == []


def test_compare_rows_tolerance_override():
    res = perf_ledger.compare_rows(
        _row("a", {"tok_s": 600.0}), _row("b", {"tok_s": 580.0}),
        tolerance={"tok_s": 0.01},
    )
    assert res["regressions"] == ["tok_s"]


# -- producers: driver-wrapper back-fill ------------------------------------


def _bench_doc(n, metric, value, vs, ttft, itl, elapsed) -> dict:
    """One recorded round in the driver's wrapper shape ({"n", "cmd",
    "rc", "tail", "parsed"}) around a bench.py payload."""
    return {
        "n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
        "parsed": {
            "metric": metric, "value": value, "unit": "tok/s",
            "vs_baseline": vs,
            "extras": {
                "platform": "cpu", "model": "tiny", "params": 106816,
                "num_requests": 16, "isl": 64, "osl": 32,
                "p50_ttft_s": ttft, "p50_itl_s": itl, "mfu": None,
                "elapsed_s": elapsed, "generated_tokens": 512,
            },
        },
    }


#: the five rounds the driver recorded before this ledger existed (the
#: retired BENCH_r01..r05.json files, trimmed to what row_from_bench
#: reads): r01 could not open its backend, r02..r05 are `tiny` on CPU
BENCH_ROUNDS = {
    "r01": {
        "n": 1, "cmd": "python bench.py", "rc": 1, "parsed": None,
        "tail": (
            "Traceback (most recent call last):\n"
            "RuntimeError: Unable to initialize backend 'some_backend': "
            "UNAVAILABLE: TPU backend setup/compile error (Unavailable).\n"
        ),
    },
    "r02": _bench_doc(
        2, "output_tok_s_per_chip", 552.57, 1.007, 0.0288, 0.02896, 0.93
    ),
    "r03": _bench_doc(
        3, "output_tok_s_per_chip", 651.55, 1.187, 0.0268, 0.02448, 0.79
    ),
    "r04": _bench_doc(
        4, "output_tok_s_cpu_fallback", 611.51, 1.114, 0.0271, 0.02613, 0.84
    ),
    "r05": _bench_doc(
        5, "output_tok_s_cpu_fallback", 534.45, 0.974, 0.0311, 0.0299, 0.96
    ),
}


def _backfill(tmp_path) -> str:
    """Back-fill r01..r05 from the recorded rounds into a fresh ledger,
    returning its path."""
    path = str(tmp_path / "ledger.jsonl")
    for round_name, doc in BENCH_ROUNDS.items():
        perf_ledger.append_row(
            perf_ledger.row_from_bench(doc, round_name), path
        )
    return path


def test_every_recorded_bench_round_parses_into_the_schema(tmp_path):
    """CI satellite: the recorded driver rounds must keep back-filling
    into valid ledger rows — a schema change that orphans them fails
    here."""
    path = _backfill(tmp_path)
    rows, problems = perf_ledger.read_rows(path, strict=True)
    assert problems == []
    by = perf_ledger.rows_by_round(rows)
    assert set(by) >= {"r01", "r02", "r03", "r04", "r05"}
    # r01 predates bench.py: rc=1, parsed null -> honest failed row
    assert by["r01"]["ok"] is False
    assert by["r01"]["metrics"] == {}
    assert by["r01"]["note"]
    for name in ("r02", "r03", "r04", "r05"):
        assert by[name]["ok"] is True
        assert by[name]["metrics"]["tok_s"] > 0
        assert by[name]["config"].get("model") == "tiny"
    # r02/r03 measured the same workload -> diffable pair
    assert by["r02"]["fingerprint"] == by["r03"]["fingerprint"]


def test_row_from_decode_profile():
    doc = {
        "platform": "cpu", "k_steps": 8, "model": "tiny",
        "batches": {
            "8": {"full_xla": {"tok_s": 900.0},
                  "pure_xla": {"ms_per_dispatch": 1.0}},
            "64": {"full_xla": {"tok_s": 2634.3},
                   "pure_xla": {"ms_per_dispatch": 766.931},
                   "full_pallas": {"tok_s": 2000.0},
                   "pure_pallas": {"ms_per_dispatch": 900.0}},
        },
    }
    row = perf_ledger.row_from_decode_profile(doc, "r06/decode")
    assert row["ok"] and row["source"] == "decode_profile"
    # headline = the LARGEST batch's best impl
    assert row["metrics"]["tok_s"] == 2634.3
    assert row["metrics"]["ms_per_dispatch"] == 766.931
    assert row["metrics"]["pallas_tok_s"] == 2000.0
    assert row["config"]["batches"] == ["8", "64"]

    empty = perf_ledger.row_from_decode_profile({"batches": {}}, "r0")
    assert empty["ok"] is False and empty["note"]


def test_row_from_baseline_pseudo_row():
    with open(REPO / "BASELINE.json") as f:
        row = perf_ledger.row_from_baseline(json.load(f))
    assert row["round"] == "BASELINE"
    assert row["metrics"]["tok_s"] == pytest.approx(6919.8)
    assert row["metrics"]["mfu"] == pytest.approx(0.2549)
    assert perf_ledger.validate_row(row) == []


def test_cli_append_bench(tmp_path, capsys):
    path = str(tmp_path / "ledger.jsonl")
    bench = tmp_path / "BENCH_r03.json"
    bench.write_text(json.dumps(BENCH_ROUNDS["r03"]))
    rc = perf_ledger.main([
        "--append-bench", str(bench),
        "--round", "r03", "--ledger", path,
    ])
    assert rc == 0
    assert "appended round=r03" in capsys.readouterr().out
    rows, _ = perf_ledger.read_rows(path, strict=True)
    assert rows[0]["metrics"]["tok_s"] == pytest.approx(651.55)


# -- scripts/perf_diff.py CI contract --------------------------------------


def test_perf_diff_exit_codes(tmp_path, capsys):
    pd = _load_perf_diff()
    path = _backfill(tmp_path)

    # r01 failed -> nothing comparable -> clean exit (acceptance)
    assert pd.main(["r01", "r05", "--ledger", path]) == 0
    assert "nothing comparable" in capsys.readouterr().out

    # same-workload rounds, both inside the band
    assert pd.main(["r02", "r03", "--ledger", path]) == 0
    assert "no regressions" in capsys.readouterr().out

    # missing round is a data error, not a pass
    assert pd.main(["r02", "r99", "--ledger", path]) == 1
    capsys.readouterr()

    # inject a 10% tok/s regression on the SAME fingerprint (acceptance)
    rows, _ = perf_ledger.read_rows(path)
    r05 = perf_ledger.rows_by_round(rows)["r05"]
    bad = perf_ledger.make_row(
        "r06", "bench",
        {"tok_s": r05["metrics"]["tok_s"] * 0.90}, r05["config"],
        platform=r05["platform"],
    )
    perf_ledger.append_row(bad, path)
    assert pd.main(["r05", "r06", "--ledger", path]) == 2
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "tok_s" in out

    # --tolerance widens the band back to passing
    assert pd.main(
        ["r05", "r06", "--ledger", path, "--tolerance", "tok_s=0.15"]
    ) == 0
    capsys.readouterr()


def test_perf_diff_baseline_and_list(tmp_path, capsys):
    pd = _load_perf_diff()
    path = _backfill(tmp_path)

    # BASELINE (TPU workload) vs a CPU round: fingerprints differ, the
    # whole diff is advisory -> exit 0 even though the delta is huge
    rc = pd.main([
        "BASELINE", "r05", "--ledger", path,
        "--baseline", str(REPO / "BASELINE.json"),
    ])
    assert rc == 0
    assert "advisory" in capsys.readouterr().out

    assert pd.main(["--list", "--ledger", path]) == 0
    out = capsys.readouterr().out
    for name in ("r01", "r02", "r03", "r04", "r05"):
        assert name in out

    # unreadable ledger is a data error
    assert pd.main(["r02", "r03", "--ledger",
                    str(tmp_path / "nope.jsonl")]) == 1


def test_perf_diff_json_output(tmp_path, capsys):
    pd = _load_perf_diff()
    path = _backfill(tmp_path)
    assert pd.main(["r02", "r03", "--ledger", path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["round_a"] == "r02" and doc["round_b"] == "r03"
    assert any(r["metric"] == "tok_s" for r in doc["rows"])
