"""scripts/doctor.py: rule-based fleet diagnosis over recorded
/v1/fleet + /v1/debug/flight snapshots (pure `diagnose()`),
the text report, and the offline CLI path."""

import importlib.util
import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_doctor():
    spec = importlib.util.spec_from_file_location(
        "doctor", REPO / "scripts" / "doctor.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(kind="decode", **kw):
    return {"seq": 0, "kind": kind, "step_ms": 1.0, "running": 4, **kw}


FLEET = {
    "workers": {
        "w-healthy": {
            "role": "decode", "last_seen_s": 0.3, "tok_s": 800.0,
            "kv_total_pages": 512, "num_running": 4, "stalls_total": 0,
        },
        "w-dead": {
            "role": "decode", "last_seen_s": 42.0, "tok_s": 0.0,
        },
        "w-stalled": {
            "role": "decode", "last_seen_s": 0.4, "tok_s": 700.0,
            "stalls_total": 2,
            "stalls_by_cause": {"engine_stuck": 2},
            "kv_total_pages": 512,
        },
        "w-thrash": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 650.0,
            "kv_total_pages": 512, "num_running": 8,
        },
        "w-storm": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 720.0,
            "kv_total_pages": 512,
        },
        "w-slow": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 50.0,
            "kv_total_pages": 512,
        },
        "w-xor": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 750.0,
            "kv_total_pages": 512,
        },
        "w-silent": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 740.0,
            "num_running": 3, "kv_total_pages": 512,
        },
        # draining (fresh): state=draining suppresses the dead/stalled
        # rules — a planned wind-down must never page. The lifetime
        # stalls_total=1 (a stall diagnosed long before the drain) must
        # NOT read as a wedged drain.
        "w-drain": {
            "role": "decode", "last_seen_s": 0.4, "tok_s": 0.0,
            "state": "draining", "num_running": 2, "stalls_total": 1,
            "kv_total_pages": 512,
        },
        # draining but WEDGED: silent past the dead threshold — a drain
        # that should long have ended still surfaces (warning), without
        # tripping dead/stalled
        "w-drain-wedged": {
            "role": "decode", "last_seen_s": 42.0, "tok_s": 0.0,
            "state": "draining", "num_running": 2, "stalls_total": 1,
            "kv_total_pages": 512,
        },
        # bounded admission actively shedding -> "raise capacity"
        "w-shed": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 760.0,
            "kv_total_pages": 512, "num_running": 4, "num_waiting": 6,
            "overload_rejects": 17, "deadline_expired": 3,
        },
        # deep queue + the role burning budget + ZERO rejects ->
        # "queue unbounded, enable admission caps"
        "w-unbounded": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 710.0,
            "kv_total_pages": 512, "num_running": 2, "num_waiting": 40,
            "overload_rejects": 0,
        },
    },
    "roles": {
        "decode": {
            "workers": 8,
            "slo": {
                "windows": {
                    "60": {"attainment": 0.95, "burn_rate": 5.0,
                           "requests": 100},
                },
            },
        },
    },
    "fleet": {"workers": 8},
}

FLIGHT = {
    "workers": {
        "w-healthy": {"records": [_rec() for _ in range(16)]},
        "w-stalled": {"records": [_rec() for _ in range(16)]},
        "w-thrash": {"records": [
            _rec(free_pages=2, watermark=511, preempted=1)
            for _ in range(16)
        ]},
        "w-storm": {"records": [
            _rec(compiles=1, compile_ms=300.0) for _ in range(16)
        ]},
        "w-slow": {"records": [_rec() for _ in range(16)]},
        # pure prefill steps while decode rows run, zero mixed steps
        "w-xor": {"records": [
            _rec(kind="prefill", n_prefill=1, running=5)
            for _ in range(16)
        ]},
        # w-silent: running requests, NO flight records
        "w-shed": {"records": [_rec() for _ in range(16)]},
        "w-unbounded": {"records": [_rec() for _ in range(16)]},
    },
}


def test_rules_fire_on_the_recorded_fleet():
    doctor = _load_doctor()
    findings = doctor.diagnose(FLEET, FLIGHT)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f["rule"], []).append(f)

    assert [f["worker"] for f in by_rule["dead-worker"]] == ["w-dead"]
    assert by_rule["dead-worker"][0]["severity"] == "critical"
    stalled = {f["worker"] for f in by_rule["stalled-worker"]}
    assert stalled == {"w-stalled", "w-silent"}
    assert [f["worker"] for f in by_rule["pool-exhaustion"]] == ["w-thrash"]
    assert [f["worker"] for f in by_rule["compile-storm"]] == ["w-storm"]
    assert [f["worker"] for f in by_rule["decode-stall"]] == ["w-xor"]
    assert [f["worker"] for f in by_rule["skewed-worker"]] == ["w-slow"]
    assert [f["evidence"]["role"] for f in by_rule["sla-burn"]] == ["decode"]
    assert "low-attainment" not in by_rule
    # overload fires in BOTH directions with opposite prescriptions
    overload = {f["worker"]: f for f in by_rule["overload"]}
    assert set(overload) == {"w-shed", "w-unbounded"}
    assert "raise capacity" in overload["w-shed"]["action"]
    assert overload["w-shed"]["evidence"]["overload_rejects"] == 17
    assert "--max-waiting" in overload["w-unbounded"]["action"]
    assert overload["w-unbounded"]["evidence"]["burn_rate"] == 5.0
    # draining: a fresh drain is an info note; one silent past the dead
    # threshold (or with stalls) escalates to warning — but neither ever
    # trips the dead/stalled rules
    draining = {f["worker"]: f for f in by_rule["draining-worker"]}
    assert set(draining) == {"w-drain", "w-drain-wedged"}
    assert draining["w-drain"]["severity"] == "info"
    assert draining["w-drain-wedged"]["severity"] == "warning"
    assert "wedged" in draining["w-drain-wedged"]["summary"]
    assert all(
        f["worker"] not in ("w-drain", "w-drain-wedged")
        for f in findings if f["rule"] in ("dead-worker", "stalled-worker")
    )
    # criticals sort first
    assert findings[0]["severity"] == "critical"
    # healthy worker triggers nothing
    assert all(f["worker"] != "w-healthy" for f in findings)


def test_handover_rules_fire_on_recorded_snapshots():
    """handover-worker / handover-stuck / handover-fallback-storm
    (ISSUE 12): a live migration is an info note with the dead/stalled
    rules suppressed; one SILENT past the dead threshold is stuck; a
    fleet whose handovers keep degrading to drain is a storm."""
    doctor = _load_doctor()
    fleet = {
        "workers": {
            "w-ho": {
                "role": "decode", "last_seen_s": 0.3, "tok_s": 500.0,
                "state": "handover", "handover_phase": "transfer",
                "num_running": 2, "handover_bytes_total": 4096,
            },
            "w-ho-stuck": {
                "role": "decode", "last_seen_s": 42.0, "tok_s": 0.0,
                "state": "handover", "handover_phase": "offer",
                "stalls_total": 1,
            },
        },
        "roles": {},
    }
    findings = doctor.diagnose(fleet, {})
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f["rule"], []).append(f)
    assert [f["worker"] for f in by_rule["handover-worker"]] == ["w-ho"]
    assert by_rule["handover-worker"][0]["severity"] == "info"
    assert "transfer" in by_rule["handover-worker"][0]["summary"]
    stuck = by_rule["handover-stuck"]
    assert [f["worker"] for f in stuck] == ["w-ho-stuck"]
    assert stuck[0]["severity"] == "warning"
    assert stuck[0]["evidence"]["handover_phase"] == "offer"
    # neither trips dead/stalled while mid-handover
    assert all(
        f["rule"] not in ("dead-worker", "stalled-worker")
        for f in findings
    )
    assert "handover-fallback-storm" not in by_rule

    # fallback storm: fleet-wide drain degradations outnumber successes
    storm = {
        "workers": {
            f"w{i}": {
                "role": "decode", "last_seen_s": 0.2, "tok_s": 500.0,
                "handover_fallbacks_total": 2, "handovers_total": 0,
            }
            for i in range(3)
        },
        "roles": {},
    }
    findings = doctor.diagnose(storm, {})
    storms = [f for f in findings if f["rule"] == "handover-fallback-storm"]
    assert len(storms) == 1 and storms[0]["severity"] == "warning"
    assert storms[0]["evidence"]["handover_fallbacks_total"] == 6
    assert "failing phase" in storms[0]["action"]
    # a healthy upgrade history (successes >= fallbacks) is quiet
    ok = {
        "workers": {
            "w0": {"role": "decode", "last_seen_s": 0.2,
                   "handovers_total": 8, "handover_fallbacks_total": 3},
        },
        "roles": {},
    }
    assert not [
        f for f in doctor.diagnose(ok, {})
        if f["rule"] == "handover-fallback-storm"
    ]


def test_migration_storm_rule_fires_on_recorded_snapshots():
    """migration-storm (ISSUE 18): the KV economy's per-prefix
    migrations thrash in two ways — transfers keep degrading to cold
    prefill (transfer plane failing), or completions fire on so large a
    share of requests that hot prefixes are ping-ponging. A healthy
    economy (occasional profitable moves, few failures) stays quiet."""
    doctor = _load_doctor()

    def storms(workers):
        return [
            f for f in doctor.diagnose(
                {"workers": workers, "roles": {}}, {}
            )
            if f["rule"] == "migration-storm"
        ]

    def w(**kw):
        return {"role": "decode", "last_seen_s": 0.2, "tok_s": 500.0,
                "kv_total_pages": 512, **kw}

    # (1) degradation storm: fallbacks outnumber completions fleet-wide
    hits = storms({
        f"w{i}": w(kv_migration_fallbacks_total=2, kv_migrations_total=1)
        for i in range(3)
    })
    assert len(hits) == 1 and hits[0]["severity"] == "warning"
    assert hits[0]["evidence"]["kv_migration_fallbacks_total"] == 6
    assert hits[0]["evidence"]["kv_migrations_total"] == 3
    assert "cold prefill" in hits[0]["summary"]
    assert "failing phase" in hits[0]["action"]

    # (2) churn storm: completions succeed but fire on >1 in 5 requests
    hits = storms({
        "w0": w(kv_migrations_total=18, requests_received=40),
        "w1": w(kv_migrations_total=12, requests_received=50),
    })
    assert len(hits) == 1 and hits[0]["severity"] == "warning"
    assert hits[0]["evidence"]["kv_migrations_total"] == 30
    assert hits[0]["evidence"]["fleet_requests_received"] == 90
    assert "ping-ponging" in hits[0]["summary"]
    assert "DYN_KV_ECONOMY_MIN_FLOPS_PER_BYTE" in hits[0]["action"]

    # healthy economy: many requests, a few profitable moves, rare
    # failures below both thresholds — quiet
    assert storms({
        "w0": w(kv_migrations_total=30, kv_migration_fallbacks_total=2,
                requests_received=1000),
    }) == []
    # a warming fleet's first few migrations never count as churn
    assert storms({
        "w0": w(kv_migrations_total=4, requests_received=5),
    }) == []


def test_tier_pressure_rule_fires_on_recorded_snapshots():
    """tier-pressure (ISSUE 18): a worker whose HBM pool is pegged at
    the watermark while its KVBM tier hits are dominated by DISK — the
    hot working set was demoted past host slab, and every warm hit now
    pays an NVMe promotion. Host-dominated hits, an unpegged pool, or a
    pool that never demoted all stay quiet."""
    doctor = _load_doctor()

    def pressure(extra):
        fleet = {"workers": {"w0": {
            "role": "decode", "last_seen_s": 0.2, "tok_s": 500.0,
            **extra,
        }}, "roles": {}}
        return [
            f for f in doctor.diagnose(fleet, {})
            if f["rule"] == "tier-pressure"
        ]

    pegged = {"kv_free_pages": 4, "kv_total_pages": 512,
              "kvbm_demotions_total": 90, "kvbm_host_blocks": 48,
              "kvbm_disk_blocks": 200}
    (f,) = pressure({**pegged, "kvbm_host_hits_total": 3,
                     "kvbm_disk_hits_total": 17})
    assert f["severity"] == "warning" and f["worker"] == "w0"
    assert "DISK" in f["summary"]
    assert f["evidence"]["kvbm_disk_hits_total"] == 17
    assert f["evidence"]["kv_free_pages"] == 4
    assert "HBM capacity" in f["action"]

    # host slab absorbing the warmth: the tiers are doing their job
    assert pressure({**pegged, "kvbm_host_hits_total": 20,
                     "kvbm_disk_hits_total": 2}) == []
    # plenty of free HBM: demotions were transient, not pressure
    assert pressure({**pegged, "kv_free_pages": 300,
                     "kvbm_host_hits_total": 3,
                     "kvbm_disk_hits_total": 17}) == []
    # pegged but never demoted (no KVBM): a pool-capacity story, not a
    # tiering one — the pool-exhaustion rule owns it
    assert pressure({"kv_free_pages": 4, "kv_total_pages": 512,
                     "kvbm_disk_hits_total": 17}) == []
    # too few tiered hits to judge the mix
    assert pressure({**pegged, "kvbm_host_hits_total": 1,
                     "kvbm_disk_hits_total": 3}) == []


def test_snapshot_only_mode_does_not_flag_busy_workers_as_stalled():
    """--snapshot without --flight: no flight doc at all — busy workers
    with no records are the NORM there, not wedged engines (the silent-
    worker rule only fires when flight data was actually collected)."""
    doctor = _load_doctor()
    findings = doctor.diagnose(FLEET, {})
    silent = [
        f for f in findings
        if f["rule"] == "stalled-worker" and f["worker"] == "w-silent"
    ]
    assert silent == []
    # the counter-sourced stalled-worker finding still fires
    assert any(
        f["rule"] == "stalled-worker" and f["worker"] == "w-stalled"
        for f in findings
    )


def _planner(**kw):
    base = {
        "mode": "ClosedLoopPlanner",
        "targets": {"decode": 4, "prefill": 1},
        "observed": {"decode": 4, "prefill": 1},
        "limits": {"min_decode": 1, "max_decode": 4,
                   "min_prefill": 0, "max_prefill": 4},
        "setpoint": {"attainment": 0.99, "burn_high": 1.0,
                     "burn_low": 0.25, "cooldown_s": 30.0,
                     "flip_cooldown_s": 60.0},
        "signals": {"burn_rate": 0.2, "sla_attainment": 0.995},
        "decisions_total": {"hold": 50},
        "flips_total": 0,
        "actions_clamped_total": 0,
        "cooldown_holds_total": 0,
        "burn_high_ticks": 0,
        "at_max": False,
        "recent_decisions": [],
    }
    base.update(kw)
    return base


def test_planner_oscillation_rule_fires_on_alternating_directions():
    doctor = _load_doctor()
    fleet = {
        "workers": {}, "roles": {}, "fleet": {"workers": 0},
        # up->down->up->down on decode, each pair 5s apart — well inside
        # the 30s cooldown the setpoint advertises: flapping
        "planner": _planner(recent_decisions=[
            {"ts": 100.0, "action": "scale_up", "role": "decode",
             "from": 2, "to": 3},
            {"ts": 105.0, "action": "scale_down", "role": "decode",
             "from": 3, "to": 2},
            {"ts": 110.0, "action": "scale_up", "role": "decode",
             "from": 2, "to": 3},
            {"ts": 115.0, "action": "scale_down", "role": "decode",
             "from": 3, "to": 2},
        ]),
    }
    findings = doctor.diagnose(fleet, {})
    osc = [f for f in findings if f["rule"] == "planner-oscillation"]
    assert len(osc) == 1, findings
    assert osc[0]["severity"] == "warning"
    assert osc[0]["evidence"]["role"] == "decode"
    assert osc[0]["evidence"]["reversals"] >= 2
    assert "hysteresis" in osc[0]["action"]


def test_planner_flip_storm_fires_inside_cooldown_window():
    doctor = _load_doctor()
    fleet = {
        "workers": {}, "roles": {}, "fleet": {"workers": 0},
        "planner": _planner(recent_decisions=[
            {"ts": 100.0, "action": "flip", "src": "prefill",
             "dst": "decode"},
            {"ts": 110.0, "action": "flip", "src": "decode",
             "dst": "prefill"},
            {"ts": 120.0, "action": "flip", "src": "prefill",
             "dst": "decode"},
        ], flips_total=3),
    }
    findings = doctor.diagnose(fleet, {})
    osc = [f for f in findings if f["rule"] == "planner-oscillation"]
    assert len(osc) == 1, findings
    assert "flip storm" in osc[0]["summary"]


def test_sla_unrecovered_fires_at_the_clamp():
    doctor = _load_doctor()
    fleet = {
        "workers": {}, "roles": {}, "fleet": {"workers": 0},
        "planner": _planner(
            burn_high_ticks=7, at_max=True,
            targets={"decode": 4, "prefill": 1},
            signals={"burn_rate": 2.3, "sla_attainment": 0.91},
        ),
    }
    findings = doctor.diagnose(fleet, {})
    unrec = [f for f in findings if f["rule"] == "sla-unrecovered"]
    assert len(unrec) == 1, findings
    assert unrec[0]["severity"] == "critical"
    assert unrec[0]["evidence"]["burn_high_ticks"] == 7
    assert "--max-decode" in unrec[0]["action"]
    # below the tick threshold, or not at the clamp: no finding
    for planner in (
        _planner(burn_high_ticks=2, at_max=True),
        _planner(burn_high_ticks=9, at_max=False),
    ):
        fleet["planner"] = planner
        assert not [
            f for f in doctor.diagnose(fleet, {})
            if f["rule"] == "sla-unrecovered"
        ]


def test_planner_rules_quiet_on_healthy_planner():
    doctor = _load_doctor()
    fleet = {
        "workers": {}, "roles": {}, "fleet": {"workers": 0},
        "planner": _planner(recent_decisions=[
            # well-spaced same-direction scaling is a healthy ramp
            {"ts": 100.0, "action": "scale_up", "role": "decode",
             "from": 2, "to": 3},
            {"ts": 200.0, "action": "scale_up", "role": "decode",
             "from": 3, "to": 4},
            {"ts": 400.0, "action": "scale_down", "role": "decode",
             "from": 4, "to": 3},
        ]),
    }
    findings = doctor.diagnose(fleet, {})
    assert not [
        f for f in findings
        if f["rule"] in ("planner-oscillation", "sla-unrecovered")
    ], findings


def test_clean_fleet_reports_all_clear():
    doctor = _load_doctor()
    fleet = {
        "workers": {
            "w1": {"role": "decode", "last_seen_s": 0.2, "tok_s": 800.0,
                   "kv_total_pages": 512},
            "w2": {"role": "decode", "last_seen_s": 0.3, "tok_s": 780.0,
                   "kv_total_pages": 512},
        },
        "roles": {}, "fleet": {"workers": 2},
    }
    flight = {"workers": {
        "w1": {"records": [_rec() for _ in range(8)]},
        "w2": {"records": [_rec() for _ in range(8)]},
    }}
    findings = doctor.diagnose(fleet, flight)
    assert findings == []
    assert "all clear" in doctor.render_report(fleet, findings)


def test_report_renders_and_cli_runs_offline(tmp_path):
    doctor = _load_doctor()
    findings = doctor.diagnose(FLEET, FLIGHT)
    text = doctor.render_report(FLEET, findings)
    assert "dynamo-tpu doctor: 12 worker(s)" in text
    assert "[CRITICAL" in text and "dead-worker" in text
    assert "compile-storm @ w-storm" in text
    assert "-> " in text  # every finding carries an action

    snap = tmp_path / "fleet.json"
    fl = tmp_path / "flight.json"
    snap.write_text(json.dumps(FLEET))
    fl.write_text(json.dumps(FLIGHT))
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "doctor.py"),
         "--snapshot", str(snap), "--flight", str(fl)],
        capture_output=True, text=True, timeout=60,
    )
    # exit code 2 signals critical findings (probe-friendly)
    assert out.returncode == 2, out.stderr
    assert "dead-worker" in out.stdout
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "doctor.py"),
         "--snapshot", str(snap), "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 2
    assert isinstance(json.loads(out.stdout), list)


def test_kv_index_drift_rule_severities():
    """kv-index-drift (ISSUE 13): info when drift was detected AND
    repaired; warning while subtrees sit stale (routing them cold);
    critical when resyncs only ever fail (the index cannot converge);
    silent with no kv_index section and on a clean converged plane."""
    doctor = _load_doctor()

    def fleet(**kv_index):
        return {"workers": {}, "roles": {}, "fleet": {"workers": 0},
                "kv_index": kv_index}

    def drift_findings(f):
        return [
            x for x in doctor.diagnose(f, {})
            if x["rule"] == "kv-index-drift"
        ]

    # repaired drift: info, evidence carries the counters
    (info,) = drift_findings(fleet(
        stale_workers=0, gaps_total=3, digest_mismatches_total=1,
        resyncs_total=4, resync_failures_total=0, drift_blocks_total=17,
    ))
    assert info["severity"] == "info"
    assert info["evidence"]["drift_blocks_total"] == 17

    # stale subtrees pending repair: warning
    (warn,) = drift_findings(fleet(
        stale_workers=2, gaps_total=5, digest_mismatches_total=0,
        resyncs_total=3, resync_failures_total=1, drift_blocks_total=9,
    ))
    assert warn["severity"] == "warning"
    assert "COLD" in warn["summary"]

    # stale + only failures: critical (cannot converge)
    (crit,) = drift_findings(fleet(
        stale_workers=1, gaps_total=2, digest_mismatches_total=0,
        resyncs_total=0, resync_failures_total=6, drift_blocks_total=0,
    ))
    assert crit["severity"] == "critical"
    assert "no-kv-sequencing" in crit["action"]

    # clean plane / missing section: quiet
    assert drift_findings(fleet(
        stale_workers=0, gaps_total=0, digest_mismatches_total=0,
        resyncs_total=0, resync_failures_total=0, drift_blocks_total=0,
    )) == []
    assert drift_findings(
        {"workers": {}, "roles": {}, "fleet": {"workers": 0}}
    ) == []


def _trace_summary(tid, total, dominant, phases, workers, reasons=None):
    return {
        "trace_id": tid, "duration_ms": total, "workers": workers,
        "kept_reasons": reasons or ["slow_e2e"],
        "breakdown": {
            "total_ms": total, "dominant": dominant, "phases": phases,
        },
    }


def test_slow_trace_attribution_rule():
    """slow-trace-attribution (fleet trace plane): the worst kept
    traces' dominant phases fold into one actionable finding per phase
    — 'p99 dominated by queue_wait on the decode pool -> scale decode'
    — while decode-dominant (just long) traces stay quiet."""
    doctor = _load_doctor()
    fleet = {
        "workers": {
            "w-dec": {"role": "decode", "last_seen_s": 0.2,
                      "tok_s": 800.0, "kv_total_pages": 512},
        },
        "roles": {}, "fleet": {"workers": 1},
    }

    def rule_findings(traces):
        return [
            f for f in doctor.diagnose(fleet, {}, traces)
            if f["rule"] == "slow-trace-attribution"
        ]

    # queue_wait-dominated worst traces on the decode pool -> one
    # warning naming the phase, the pool, and the worst trace id
    traces = {"traces": [
        _trace_summary("a1" * 16, 5000.0, "queue_wait",
                       {"queue_wait": 4000.0, "decode": 1000.0},
                       ["w-dec"]),
        _trace_summary("b2" * 16, 3000.0, "queue_wait",
                       {"queue_wait": 2000.0, "decode": 1000.0},
                       ["w-dec"]),
        _trace_summary("c3" * 16, 400.0, "decode", {"decode": 400.0},
                       ["w-dec"], reasons=["healthy_sample"]),
    ]}
    (f,) = rule_findings(traces)
    assert f["severity"] == "warning"
    assert "queue_wait" in f["summary"]
    assert "decode pool" in f["summary"]
    assert "a1" * 16 in f["summary"]  # the worst trace is named
    assert "scale" in f["action"]
    assert len(f["evidence"]["traces"]) == 2

    # decode-dominant traces are just long generations: no finding
    assert rule_findings({"traces": [
        _trace_summary("d4" * 16, 9000.0, "decode", {"decode": 9000.0},
                       ["w-dec"]),
    ]}) == []

    # a dominant phase below the share floor does not attribute
    assert rule_findings({"traces": [
        _trace_summary("e5" * 16, 1000.0, "queue_wait",
                       {"queue_wait": 200.0, "decode": 150.0,
                        "prefill": 150.0, "other": 500.0},
                       ["w-dec"]),
    ]}) == []

    # transfer-dominated -> the disagg-plane action, no pool suffix
    # when workers span roles unknown to the snapshot
    (t,) = rule_findings({"traces": [
        _trace_summary("f6" * 16, 2000.0, "transfer",
                       {"transfer": 1500.0, "decode": 500.0},
                       ["w-unknown"]),
    ]})
    assert "transfer plane" in t["action"]
    assert "the  pool" not in t["summary"]  # no half-formed pool suffix

    # absent/garbage trace docs: quiet
    assert rule_findings(None) == []
    assert rule_findings({"traces": "garbage"}) == []


def test_control_plane_degraded_rule_severities():
    doctor = _load_doctor()
    # metrics service degraded AND every worker's frames stale -> the
    # whole fleet is broker-less: critical
    fleet = {
        "workers": {"w1": {"role": "decode", "last_seen_s": 42.0}},
        "control_plane": {
            "degraded": True, "disconnected_s": 12.0,
            "addresses": ["a:4222", "b:4222"], "degraded_total": 1,
        },
    }
    hits = [
        f for f in doctor.diagnose(fleet, {})
        if f["rule"] == "control-plane-degraded"
    ]
    assert hits and hits[0]["severity"] == "critical"
    assert hits[0]["evidence"]["workers_stale"] is True

    # degraded metrics service but FRESH worker frames (partial
    # partition) -> warning
    fleet2 = {
        "workers": {
            "w1": {"role": "decode", "last_seen_s": 0.2, "tok_s": 500.0,
                   "kv_total_pages": 512},
        },
        "control_plane": {"degraded": True, "disconnected_s": 6.0},
    }
    hits2 = [
        f for f in doctor.diagnose(fleet2, {})
        if f["rule"] == "control-plane-degraded"
    ]
    assert hits2 and hits2[0]["severity"] == "warning"

    # ONE worker reporting broker-less mode while the service is fine
    # -> per-worker warning naming the drop counters
    fleet3 = {
        "workers": {
            "w1": {"role": "decode", "last_seen_s": 0.2, "tok_s": 500.0,
                   "kv_total_pages": 512, "degraded": 1,
                   "kv_events_dropped_total": 7, "kv_events_pending": 12,
                   "degraded_entries_total": 2},
        },
        "control_plane": {"degraded": False},
    }
    hits3 = [
        f for f in doctor.diagnose(fleet3, {})
        if f["rule"] == "control-plane-degraded"
    ]
    assert len(hits3) == 1
    assert hits3[0]["worker"] == "w1"
    assert hits3[0]["severity"] == "warning"
    assert hits3[0]["evidence"]["kv_events_dropped_total"] == 7


def test_replication_lag_rule():
    doctor = _load_doctor()
    base = {"workers": {}, "control_plane": {
        "degraded": False,
        "broker": {"repl_subscribers": 1, "repl_lag_records": 1000,
                   "fence": 1},
    }}
    hits = [
        f for f in doctor.diagnose(base, {})
        if f["rule"] == "replication-lag"
    ]
    assert hits and hits[0]["severity"] == "warning"
    assert "standby" in hits[0]["summary"]

    # small lag: healthy replication, quiet
    base["control_plane"]["broker"]["repl_lag_records"] = 3
    assert not [
        f for f in doctor.diagnose(base, {})
        if f["rule"] == "replication-lag"
    ]
    # no standby attached: lag is meaningless, quiet
    base["control_plane"]["broker"] = {
        "repl_subscribers": 0, "repl_lag_records": 99999,
    }
    assert not [
        f for f in doctor.diagnose(base, {})
        if f["rule"] == "replication-lag"
    ]


def test_host_skew_rule_names_the_straggler_host():
    """host-skew (ISSUE 19): two hosts reporting dispatch p95, one
    1.5x+ slower than the fastest -> one warning naming the host and
    its workers; single-host fleets stay quiet."""
    doctor = _load_doctor()
    fleet = {"workers": {
        "w-h0a": {"role": "decode", "last_seen_s": 0.2, "tok_s": 700.0,
                  "host": 0, "dispatch_p95_ms": 8.0,
                  "kv_total_pages": 512},
        "w-h0b": {"role": "decode", "last_seen_s": 0.2, "tok_s": 710.0,
                  "host": 0, "dispatch_p95_ms": 7.5,
                  "kv_total_pages": 512},
        "w-h1": {"role": "decode", "last_seen_s": 0.2, "tok_s": 690.0,
                 "host": 1, "dispatch_p95_ms": 26.0,
                 "kv_total_pages": 512},
    }}
    hits = [
        f for f in doctor.diagnose(fleet, {})
        if f["rule"] == "host-skew"
    ]
    assert len(hits) == 1, hits
    assert hits[0]["severity"] == "warning"
    assert hits[0]["evidence"]["host"] == "1"
    assert hits[0]["evidence"]["workers"] == ["w-h1"]
    assert "/v1/debug/mesh" in hits[0]["action"]

    # a dead worker's frame must not drive the skew verdict
    fleet["workers"]["w-h1"]["last_seen_s"] = 42.0
    assert not [
        f for f in doctor.diagnose(fleet, {})
        if f["rule"] == "host-skew"
    ]

    # single host: no comparison to make
    single = {"workers": {
        k: dict(v, host=0, last_seen_s=0.2)
        for k, v in fleet["workers"].items()
    }}
    assert not [
        f for f in doctor.diagnose(single, {})
        if f["rule"] == "host-skew"
    ]


def test_host_skew_rule_ignores_sub_floor_p95():
    """Microsecond-scale CPU-test dispatches skew wildly in relative
    terms; the absolute floor keeps the rule quiet there."""
    doctor = _load_doctor()
    fleet = {"workers": {
        "w-a": {"role": "decode", "last_seen_s": 0.2, "tok_s": 700.0,
                "host": 0, "dispatch_p95_ms": 0.4,
                "kv_total_pages": 512},
        "w-b": {"role": "decode", "last_seen_s": 0.2, "tok_s": 700.0,
                "host": 1, "dispatch_p95_ms": 2.0,
                "kv_total_pages": 512},
    }}
    assert not [
        f for f in doctor.diagnose(fleet, {})
        if f["rule"] == "host-skew"
    ]


def test_perf_regression_rule_fires_on_same_fingerprint_drop():
    """perf-regression (ISSUE 19): consecutive ok rounds with the SAME
    config fingerprint, tok_s down 17% -> one warning pointing at
    scripts/perf_diff.py; a workload change (different fingerprint)
    stays quiet."""
    doctor = _load_doctor()
    from dynamo_tpu.telemetry import perf_ledger

    cfg = {"model": "tiny", "isl": 64}
    rows = [
        perf_ledger.make_row("rA", "bench", {"tok_s": 600.0}, cfg),
        perf_ledger.make_row("rB", "bench", {"tok_s": 500.0}, cfg),
    ]
    hits = [
        f for f in doctor.diagnose({"workers": {}}, {}, {}, rows)
        if f["rule"] == "perf-regression"
    ]
    assert len(hits) == 1, hits
    assert hits[0]["evidence"]["round_b"] == "rB"
    assert "tok_s" in hits[0]["evidence"]["regressions"]
    assert "perf_diff.py rA rB" in hits[0]["action"]

    # same drop across a workload change: apples to oranges, quiet
    rows[1] = perf_ledger.make_row(
        "rB", "bench", {"tok_s": 500.0}, {"model": "large", "isl": 64}
    )
    assert not [
        f for f in doctor.diagnose({"workers": {}}, {}, {}, rows)
        if f["rule"] == "perf-regression"
    ]

    # in-band drift: quiet
    rows[1] = perf_ledger.make_row("rB", "bench", {"tok_s": 580.0}, cfg)
    assert not [
        f for f in doctor.diagnose({"workers": {}}, {}, {}, rows)
        if f["rule"] == "perf-regression"
    ]


def test_cli_ledger_path_offline(tmp_path):
    """`python scripts/doctor.py --snapshot ... --ledger ...` loads the
    ledger without the package on sys.path and reports the regression."""
    from dynamo_tpu.telemetry import perf_ledger

    cfg = {"model": "tiny"}
    ledger = tmp_path / "perf_ledger.jsonl"
    for name, tok_s in (("rA", 600.0), ("rB", 480.0)):
        perf_ledger.append_row(
            perf_ledger.make_row(name, "bench", {"tok_s": tok_s}, cfg),
            str(ledger),
        )
    snap = tmp_path / "fleet.json"
    snap.write_text(json.dumps({"workers": {}}))
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "doctor.py"),
         "--snapshot", str(snap), "--ledger", str(ledger), "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr  # warning, not critical
    findings = json.loads(out.stdout)
    assert any(f["rule"] == "perf-regression" for f in findings), findings
