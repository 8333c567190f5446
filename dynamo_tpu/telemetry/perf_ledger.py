"""Perf-regression ledger: schema-versioned performance rows on disk.

Every benchmark surface in the repo (bench.py, scripts/
tpu_decode_profile.py) appends one row per run to
``artifacts/perf_ledger.jsonl`` — an append-only JSONL file that turns
scattered driver-round / artifacts/tpu/*.json artifacts into one
diffable performance history. ``scripts/perf_diff.py`` compares any two
rounds (or a round vs BASELINE.json) with per-metric tolerance bands
and exits nonzero on regression; the doctor's perf-regression rule
wraps the same comparison (docs/observability.md "Reading the perf
plane").

Row schema (version 1):

  {"schema": 1, "round": "r03", "source": "bench", "ok": true,
   "platform": "tpu", "ts": null,
   "config": {"model": "tiny", "isl": 64, ...},
   "fingerprint": "1a2b3c4d5e6f",      # sha256 of canonical config
   "metrics": {"tok_s": 651.55, "mfu": 0.021, ...},
   "note": null}

``metrics`` is an open name→number map — rows carry whatever the
producing surface measured (tok_s, p50_ttft_s, p50_itl_s, mfu,
ms_per_dispatch, attainment, hbm_peak_bytes, ...). A failed run still
gets a row (``ok: false``, empty metrics, the error in ``note``) so the
ledger records that the round happened; diffs treat such rows as having
nothing to compare. ``config`` + ``fingerprint`` let a diff flag
apples-to-oranges comparisons (different model/workload) instead of
silently reporting a "regression" that is really a config change.

The direction table below says which way is better per metric — a diff
without it can't tell a tok/s drop from a TTFT drop.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
from typing import Optional

SCHEMA_VERSION = 1

#: repo-relative default; producers resolve against the repo root (the
#: directory bench.py runs from) so rows from every surface land in ONE
#: file
DEFAULT_LEDGER = os.path.join("artifacts", "perf_ledger.jsonl")

#: +1 = higher is better (throughput-like), -1 = lower is better
#: (latency/footprint-like). Metrics absent here are reported in diffs
#: but never flagged as regressions — direction unknown.
METRIC_DIRECTION = {
    "tok_s": +1,
    "mfu": +1,
    "attainment": +1,
    "vs_baseline": +1,
    "spec_accept_rate": +1,
    "p50_ttft_s": -1,
    "p50_itl_s": -1,
    "ms_per_dispatch": -1,
    "ms_per_token_row": -1,
    "hbm_peak_bytes": -1,
    "compile_ms": -1,
}

#: fractional tolerance band per metric before a worse-direction delta
#: counts as a regression. Throughput on shared CI boxes jitters a few
#: percent run-to-run (BENCH_r04→r05 moved 12% on the same code); the
#: default band is deliberately wider than single-run noise.
DEFAULT_TOLERANCE = 0.08
METRIC_TOLERANCE = {
    "tok_s": 0.08,
    "mfu": 0.08,
    "attainment": 0.05,
    "p50_ttft_s": 0.15,
    "p50_itl_s": 0.15,
    "ms_per_dispatch": 0.15,
    "hbm_peak_bytes": 0.02,
}

_REQUIRED_FIELDS = ("schema", "round", "source", "ok", "metrics", "config")


def config_fingerprint(config: dict) -> str:
    """Stable 12-hex-digit fingerprint of a config dict (sorted-key
    canonical JSON). Two rows with the same fingerprint measured the
    same workload; differing fingerprints make a diff advisory."""
    blob = json.dumps(config, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def make_row(
    round_name: str,
    source: str,
    metrics: dict,
    config: dict,
    ok: bool = True,
    platform: Optional[str] = None,
    ts: Optional[str] = None,
    note: Optional[str] = None,
) -> dict:
    """Build a schema-current row. ``metrics`` values must be finite
    numbers; Nones and NaNs are dropped rather than stored (a diff
    can't band-compare them)."""
    clean = {}
    for k, v in (metrics or {}).items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        if v != v:  # NaN
            continue
        clean[str(k)] = v
    config = dict(config or {})
    return {
        "schema": SCHEMA_VERSION,
        "round": str(round_name),
        "source": str(source),
        "ok": bool(ok),
        "platform": platform,
        "ts": ts,
        "config": config,
        "fingerprint": config_fingerprint(config),
        "metrics": clean,
        "note": note,
    }


def validate_row(row: dict) -> list:
    """Schema check → list of human-readable problems (empty = valid)."""
    errs = []
    if not isinstance(row, dict):
        return ["row is not an object"]
    for f in _REQUIRED_FIELDS:
        if f not in row:
            errs.append(f"missing field {f!r}")
    if errs:
        return errs
    if row["schema"] != SCHEMA_VERSION:
        errs.append(
            f"schema {row['schema']!r} != {SCHEMA_VERSION} "
            "(bump needs a migration note in docs/migrating.md)"
        )
    if not isinstance(row["round"], str) or not row["round"]:
        errs.append("round must be a non-empty string")
    if not isinstance(row["ok"], bool):
        errs.append("ok must be a bool")
    if not isinstance(row["metrics"], dict):
        errs.append("metrics must be an object")
    else:
        for k, v in row["metrics"].items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                errs.append(f"metric {k!r} is not a number")
    if not isinstance(row["config"], dict):
        errs.append("config must be an object")
    elif row.get("fingerprint") != config_fingerprint(row["config"]):
        errs.append("fingerprint does not match config")
    return errs


def append_row(row: dict, path: str = DEFAULT_LEDGER) -> None:
    """Validate then append one JSON line. Raises ValueError on an
    invalid row — a corrupt producer must fail loudly, not poison the
    ledger every run."""
    errs = validate_row(row)
    if errs:
        raise ValueError(f"invalid ledger row: {'; '.join(errs)}")
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")


def read_rows(path: str, strict: bool = False):
    """Read a ledger → (rows, problems). Tolerant by default: a
    malformed line is reported in ``problems`` and skipped, so one bad
    append never bricks every future diff. ``strict=True`` raises
    instead (the schema round-trip test uses it)."""
    rows, problems = [], []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError as e:
                problems.append(f"line {ln}: bad JSON ({e})")
                if strict:
                    raise ValueError(problems[-1])
                continue
            errs = validate_row(row)
            if errs:
                problems.append(f"line {ln}: {'; '.join(errs)}")
                if strict:
                    raise ValueError(problems[-1])
                continue
            rows.append(row)
    return rows, problems


def rows_by_round(rows) -> dict:
    """round → latest row for that round (file order; last wins —
    re-running a round supersedes its earlier rows)."""
    out: dict = {}
    for r in rows:
        out[r["round"]] = r
    return out


def compare_rows(row_a: dict, row_b: dict, tolerance: dict = None) -> dict:
    """Pure comparison → {"comparable", "advisory", "rows": [...],
    "regressions": [names]}. Shared by scripts/perf_diff.py and the
    doctor's perf-regression rule."""
    tol = dict(tolerance or {})
    out = {
        "round_a": row_a["round"], "round_b": row_b["round"],
        "comparable": True, "advisory": False, "note": None,
        "rows": [], "regressions": [],
    }
    if not row_a["ok"] or not row_b["ok"]:
        bad = row_a["round"] if not row_a["ok"] else row_b["round"]
        out["comparable"] = False
        out["note"] = f"round {bad} failed (ok=false) — nothing to compare"
        return out
    if row_a.get("fingerprint") != row_b.get("fingerprint"):
        # e.g. TPU round vs CPU-fallback round: report deltas but never
        # fail CI over a workload change
        out["advisory"] = True
        out["note"] = (
            "config fingerprints differ "
            f"({row_a.get('fingerprint')} vs {row_b.get('fingerprint')}) — "
            "advisory only, no regression verdicts"
        )
    shared = sorted(set(row_a["metrics"]) & set(row_b["metrics"]))
    if not shared:
        out["comparable"] = False
        out["note"] = out["note"] or "no shared metrics between rounds"
        return out
    for name in shared:
        a, b = float(row_a["metrics"][name]), float(row_b["metrics"][name])
        direction = METRIC_DIRECTION.get(name)
        band = tol.get(
            name,
            METRIC_TOLERANCE.get(
                name, DEFAULT_TOLERANCE
            ),
        )
        rel = (b - a) / abs(a) if a else (0.0 if b == a else float("inf"))
        # worse-direction magnitude: positive means B is worse than A
        worse = -rel * direction if direction else 0.0
        verdict = "n/a"
        if direction is not None:
            if worse > band:
                verdict = "REGRESSION"
            elif worse < -band:
                verdict = "improved"
            else:
                verdict = "ok"
        out["rows"].append({
            "metric": name, "a": a, "b": b,
            "rel": rel, "band": band, "verdict": verdict,
        })
        if verdict == "REGRESSION" and not out["advisory"]:
            out["regressions"].append(name)
    # one-sided metrics: visible, never verdicted
    for name in sorted(set(row_a["metrics"]) ^ set(row_b["metrics"])):
        side = "a" if name in row_a["metrics"] else "b"
        out["rows"].append({
            "metric": name,
            "a": row_a["metrics"].get(name),
            "b": row_b["metrics"].get(name),
            "rel": None, "band": None,
            "verdict": f"only in {side}",
        })
    return out


# -- producers: one row builder per benchmark surface ----------------------

#: bench.py extras keys that are workload identity (the config
#: fingerprint), not measurements. attention_impl is deliberately NOT
#: here — it records which impl bench CHOSE (code behavior, not
#: workload), and fingerprinting it would make every auto-selection
#: change look like a different workload. kv_quantize IS identity: a
#: quantized-KV run must not diff clean against an unquantized one.
_BENCH_CONFIG_KEYS = (
    "platform", "model", "params", "num_requests", "isl", "osl",
    "kv_quantize",
)

#: bench.py payload/extras keys that are band-comparable measurements
_BENCH_METRIC_KEYS = (
    "p50_ttft_s", "p50_itl_s", "mfu", "attainment", "hbm_peak_bytes",
    "decode_dispatch_ms", "decode_sync_ms", "decode_host_ms",
)


def row_from_bench(doc: dict, round_name: str, source: str = "bench") -> dict:
    """Build a row from a bench.py emission — either the bare payload
    ``{"metric", "value", "unit", "vs_baseline", "extras"}`` or the
    BENCH_r*.json driver wrapper ``{"n", "cmd", "rc", "tail",
    "parsed"}``. A failed round (rc != 0 / parsed null) becomes an
    ``ok: false`` row with the error's last line in ``note`` — the
    ledger records every round, diffs skip the empty ones."""
    payload = doc
    note = None
    if "parsed" in doc or "rc" in doc:  # driver wrapper
        payload = doc.get("parsed")
        if payload is None or doc.get("rc", 0) != 0:
            tail = (doc.get("tail") or "").strip().splitlines()
            note = tail[-1][:200] if tail else "round failed, no output"
            return make_row(
                round_name, source, {}, {"cmd": doc.get("cmd")},
                ok=False, note=note,
            )
    extras = payload.get("extras") or {}
    config = {"metric": payload.get("metric"), "unit": payload.get("unit")}
    for k in _BENCH_CONFIG_KEYS:
        if k in extras:
            config[k] = extras[k]
    metrics = {"tok_s": payload.get("value")}
    if "vs_baseline" in payload:
        metrics["vs_baseline"] = payload["vs_baseline"]
    for k in _BENCH_METRIC_KEYS:
        if k in extras:
            metrics[k] = extras[k]
    return make_row(
        round_name, source, metrics, config,
        ok="error" not in payload,
        platform=extras.get("platform"),
        note=payload.get("error"),
    )


def row_from_decode_profile(doc: dict, round_name: str) -> dict:
    """Build a row from scripts/tpu_decode_profile.py's
    decode_profile.json: headline tok_s / ms_per_dispatch from the
    LARGEST batch's best impl (the serving-shaped point), per-impl
    detail under prefixed names."""
    batches = doc.get("batches") or {}
    config = {
        "platform": doc.get("platform"),
        "model": doc.get("model"),
        "k_steps": doc.get("k_steps"),
        "batches": sorted(batches, key=lambda b: int(b)),
    }
    metrics: dict = {}
    if batches:
        largest = max(batches, key=lambda b: int(b))
        row = batches[largest]
        best = None
        for impl in ("xla", "pallas"):
            full = row.get(f"full_{impl}") or {}
            pure = row.get(f"pure_{impl}") or {}
            if "tok_s" in full:
                metrics[f"{impl}_tok_s"] = full["tok_s"]
            if "ms_per_dispatch" in pure:
                metrics[f"{impl}_ms_per_dispatch"] = pure["ms_per_dispatch"]
            if "tok_s" in full and (best is None or full["tok_s"] > best[0]):
                best = (full["tok_s"], pure.get("ms_per_dispatch"))
        if best is not None:
            metrics["tok_s"] = best[0]
            if best[1] is not None:
                metrics["ms_per_dispatch"] = best[1]
    return make_row(
        round_name, "decode_profile", metrics, config,
        ok=bool(metrics), platform=doc.get("platform"),
        note=None if metrics else "no batches profiled",
    )


def row_from_baseline(doc: dict, round_name: str = "BASELINE") -> dict:
    """Pseudo-row from BASELINE.json's ``published`` block so perf_diff
    can compare a live round against the repo's recorded bar."""
    pub = doc.get("published") or {}
    metrics = {
        "tok_s": pub.get("output_tok_s_per_chip"),
        "p50_ttft_s": pub.get("p50_ttft_s"),
        "mfu": pub.get("mfu"),
    }
    config = {
        "metric": "output_tok_s_per_chip",
        "workload": pub.get("workload"),
        "platform": "tpu",
    }
    return make_row(
        round_name, "baseline", metrics, config, platform="tpu",
        note=pub.get("recorded"),
    )


def main(argv=None) -> int:
    """CLI for shell producers:
    ``python -m dynamo_tpu.telemetry.perf_ledger --append-bench
    artifacts/tpu/bench_1b.json --round r06`` appends one validated
    row; --append-decode-profile does the same for profile JSON."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--append-bench", metavar="FILE")
    ap.add_argument("--append-decode-profile", metavar="FILE")
    ap.add_argument("--round", dest="round_name")
    ap.add_argument("--source", default=None)
    ap.add_argument("--ledger", default=DEFAULT_LEDGER)
    args = ap.parse_args(argv)
    src = args.append_bench or args.append_decode_profile
    if not src or not args.round_name:
        ap.error("need --round and one of --append-bench / "
                 "--append-decode-profile")
    try:
        with open(src) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_ledger: cannot read {src}: {e}", file=sys.stderr)
        return 1
    if args.append_bench:
        row = row_from_bench(doc, args.round_name,
                             source=args.source or "bench")
    else:
        row = row_from_decode_profile(doc, args.round_name)
    append_row(row, args.ledger)
    print(f"perf_ledger: appended round={row['round']} "
          f"source={row['source']} ok={row['ok']} "
          f"metrics={sorted(row['metrics'])} -> {args.ledger}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
