#!/usr/bin/env python3
"""doctor: one-shot rule-based diagnosis of a dynamo-tpu fleet.

Snapshots the metrics service's `/v1/fleet` and `/v1/debug/flight`,
runs the rule set below over them, and prints one human-readable
report — the "why is this worker slow/stuck" companion
to fleet_top's "what are the numbers" view:

    python scripts/doctor.py --url http://127.0.0.1:9091
    python scripts/doctor.py --snapshot fleet.json --flight flight.json

Rules (each emits severity + worker + evidence + suggested action):
  compile-storm        compile events keep firing in the recent flight
                       window — the program family is churning in steady
                       state (every miss is a full XLA compile)
  pool-exhaustion      free pages pinned at ~0 with the watermark at
                       capacity and/or preemption-by-recompute firing in
                       the window — the KV pool is too small for the
                       workload (preemption thrash burns recompute)
  stalled-worker       the stall watchdog diagnosed wedged streams
                       (stalls_total > 0), or a worker with running
                       requests shows no flight activity
  decode-stall         pure prefill steps are interleaving with decode
                       rows waiting (mixed steps off or ineffective) —
                       running requests pay whole prefill drains as ITL
  dead-worker          a worker stopped publishing (last_seen_s beyond
                       the threshold)
  draining-worker      a worker reports state=draining (planned wind-
                       down via SIGTERM / POST /v1/admin/drain) — an
                       info note, and the dead/stalled rules are
                       suppressed for it so a drain never pages
  handover-worker /    a worker reports state=handover (live KV
  handover-stuck       migration, POST /v1/admin/handover) — info while
                       fresh; escalates to handover-stuck when it went
                       SILENT past the dead threshold mid-migration
                       (the fallback-to-drain path should have ended it)
  handover-fallback-   handovers keep degrading to plain drain fleet-
  storm                wide — successors refusing or the transfer plane
                       failing; upgrades silently lose their warm-KV
                       benefit
  migration-storm      the KV economy's per-prefix migrations are
                       thrashing fleet-wide: transfers keep degrading to
                       cold prefill (the transfer plane is failing), or
                       migrations fire on so large a share of requests
                       that the same hot prefixes must be ping-ponging
                       between workers (backoff / break-even threshold
                       misconfigured)
  tier-pressure        a worker's HBM pool is pegged while its KVBM
                       tier traffic is dominated by DISK hits — the hot
                       working set has been demoted past host slab and
                       every warm hit now pays an NVMe promotion; the
                       fix is HBM capacity (or a higher demotion
                       threshold), not more tiering
  overload             bounded admission is rejecting (overload_rejects
                       climbing -> "shedding, raise capacity"), or the
                       waiting queue is deep while the role burns its
                       SLO budget with ZERO rejects -> "queue unbounded,
                       enable admission caps" (docs/operations.md)
  skewed-worker        one worker's token throughput sits far below its
                       role's mean — a limping replica drags the whole
                       pool's SLA
  sla-burn             a role is burning its error budget (burn rate >1
                       in the merged windows)
  kv-index-drift       the KV-aware routers' prefix index detected
                       sequence gaps / digest drift: info when repaired
                       (resyncs converged), warning while subtrees sit
                       stale (those workers route cold), critical when
                       resyncs keep failing and the index cannot
                       converge
  planner-oscillation  the closed-loop planner's recent decisions
                       alternate scale directions on one role (or flips
                       storm) inside the cooldown window — hysteresis /
                       cooldown knobs are misconfigured and the fleet
                       is thrashing spawn/drain cycles
  sla-unrecovered      the planner has been at its max_decode clamp for
                       N+ consecutive ticks while the fleet still burns
                       its SLO budget — scaling is out of headroom; the
                       fix is capacity or shedding, not the loop
  slow-trace-          the N worst KEPT traces (metrics service
  attribution          GET /v1/traces?sort=duration — the tail sampler
                       keeps every anomalous trace) are dominated by an
                       actionable phase: queue_wait -> scale the pool /
                       cap admission, transfer -> check the disagg
                       planes, dispatch -> router retries, decode_stall
                       -> enable mixed steps, replay_gap -> worker churn
  control-plane-       the broker is unreachable (or a worker reports
  degraded             broker-less degraded mode): the fleet serves from
                       cached discovery, KV scores go stale-cold, the
                       planner HOLDs — warning while frames are still
                       fresh / one worker degraded, CRITICAL when the
                       metrics service itself is degraded and the whole
                       fleet's frames have gone stale (docs/operations.md
                       "Control-plane HA")
  replication-lag      the warm standby's acked replication watermark
                       trails the primary's journal by more than the
                       threshold — promoting NOW would lose that tail
                       (leases/keys/ring records); hold the failover or
                       find the lagging link

`diagnose()` is pure (snapshots in, findings out) and unit-tested
against recorded snapshots in tests/test_doctor.py. Dependency-free
(urllib only), like fleet_top.
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request
from typing import Optional

#: last_seen_s beyond this = the worker stopped publishing
DEAD_AFTER_S = 10.0
#: fraction of the role-mean tok/s below which a worker counts as skewed
SKEW_FRACTION = 0.25
#: compile events in more than this fraction of the recent window's
#: steps = the program family is churning, not warming up
COMPILE_STORM_FRACTION = 0.3
#: free pages at or below this fraction of total = exhausted
POOL_FREE_FRACTION = 0.02
#: waiting queue deeper than max(this, 4x running) while the role burns
#: its SLO budget = saturated with no admission caps
QUEUE_DEPTH_FLOOR = 8
#: consecutive burn-above-band ticks at the max_decode clamp before
#: sla-unrecovered fires
BURN_UNRECOVERED_TICKS = 5
#: direction reversals (up->down->up on one role) inside the oscillation
#: window before planner-oscillation fires
OSCILLATION_REVERSALS = 2
#: flip pairs inside the flip oscillation window before a storm fires
FLIP_STORM_COUNT = 2
#: the oscillation window is this multiple of the advertised cooldown:
#: ControlRunner already ENFORCES the cooldown (recorded same-role
#: decisions are never closer than cooldown_s apart), so the thrash
#: signature is a reversal landing shortly AFTER each cooldown expiry —
#: up at t, down at t+cooldown, up at t+2*cooldown. Comparing against
#: the bare cooldown would make the rule unsatisfiable.
OSCILLATION_WINDOW_FACTOR = 3.0
#: standby replication lag (records behind the primary's journal) above
#: which the standby is not safe to promote
REPL_LAG_WARN_RECORDS = 256
#: fallback window (seconds) when the frame advertises no cooldown
OSCILLATION_WINDOW_FLOOR_S = 60.0
#: handover drain-fallbacks (exceeding completions) before the
#: fallback-storm rule fires
FALLBACK_STORM_COUNT = 3
#: per-prefix migration fallbacks (exceeding completions) before
#: migration-storm's transfer-failure branch fires
MIGRATION_FALLBACK_STORM_COUNT = 3
#: completed migrations below this never count as churn — a warming
#: fleet legitimately migrates its first few hot prefixes
MIGRATION_CHURN_FLOOR = 10
#: completed migrations per fleet request above which the same hot
#: prefixes must be ping-ponging between workers (the router's backoff
#: window or break-even threshold is set too loose)
MIGRATION_CHURN_RATIO = 0.2
#: tiered (host+disk) KV hits before tier-pressure can judge the mix
TIER_HIT_FLOOR = 8
#: disk share of tiered hits above which the hot working set has been
#: demoted past the host slab onto NVMe
TIER_DISK_HIT_SHARE = 0.5
#: host-skew (multi-host SPMD stragglers): a host whose worst dispatch
#: p95 exceeds the fastest host's by this ratio is a straggler — under
#: lockstep SPMD every dispatch waits for it (docs/observability.md
#: "Reading the perf plane")
HOST_SKEW_RATIO = 1.5
#: dispatch p95s below this never count as skew — sub-threshold jitter
#: on near-idle hosts is noise, not a straggler
HOST_SKEW_FLOOR_MS = 5.0
#: worst kept traces the slow-trace-attribution rule examines
TRACE_WORST_N = 5
#: a phase must explain at least this share of a trace's wall time to
#: count as its dominant phase for attribution
TRACE_DOMINANT_SHARE = 0.4
#: traces shorter than this never attribute — a 2 ms admin call is
#: trivially "dominated" by whatever it did, not a latency problem
TRACE_MIN_TOTAL_MS = 50.0
#: dominant-phase -> what to do about it. decode/prefill-dominant slow
#: traces are just long generations — not findings.
TRACE_PHASE_ACTIONS = {
    "queue_wait": (
        "requests spend their time waiting for admission — scale the "
        "pool up (planner --mode closed does this on burn) or enable "
        "admission caps (--max-waiting / --max-inflight) so excess "
        "load answers 429 instead of queueing"
    ),
    "transfer": (
        "the disagg KV hand-off dominates — check which transfer plane "
        "requests actually ride (dynamo_tpu_worker_kv_transfer_*: a "
        "device/shm plane silently falling back to inline host doubles "
        "the hand-off) and the prefill queue depth"
    ),
    "dispatch": (
        "router dispatch overhead dominates — workers are refusing or "
        "down (read the router.dispatch spans' mark_down/overloaded "
        "events and retry_backoff_ms in the kept traces)"
    ),
    "decode_stall": (
        "prefill-induced decode stalls dominate — enable mixed steps "
        "(drop --no-mixed-steps) so decode rows keep emitting while "
        "prompt bursts drain (docs/engine.md 'Mixed steps')"
    ),
    "replay_gap": (
        "time lost between stream-replay attempts dominates — workers "
        "are dying mid-stream; GET /v1/fleet/events names the kills/"
        "handovers these traces overlapped"
    ),
}


def _finding(severity: str, rule: str, worker: Optional[str], summary: str,
             evidence: dict, action: str) -> dict:
    return {
        "severity": severity, "rule": rule, "worker": worker,
        "summary": summary, "evidence": evidence, "action": action,
    }


def _flight_records(flight: dict, iid: str) -> list[dict]:
    w = (flight or {}).get("workers", {}).get(iid) or {}
    recs = w.get("records")
    return recs if isinstance(recs, list) else []


def diagnose(
    fleet: dict,
    flight: Optional[dict] = None,
    traces: Optional[dict] = None,
    ledger: Optional[list] = None,
) -> list[dict]:
    """Pure rule pass: (/v1/fleet, /v1/debug/flight, /v1/traces)
    snapshots [+ perf-ledger rows] -> ordered findings
    (severity: critical > warning > info)."""
    findings: list[dict] = []
    workers = (fleet or {}).get("workers") or {}
    roles = (fleet or {}).get("roles") or {}
    findings.extend(_control_plane_rules(fleet, workers))
    #: flight data present at all? The silent-worker rule needs the
    #: distinction between "no flight doc" and "enabled but silent"
    flight_collected = bool((flight or {}).get("workers"))

    # per-role token-throughput means for the skew rule
    role_tok: dict[str, list[float]] = {}
    for iid, w in workers.items():
        role_tok.setdefault(str(w.get("role", "?")), []).append(
            float(w.get("tok_s") or 0.0)
        )
    role_mean = {
        r: (sum(v) / len(v) if v else 0.0) for r, v in role_tok.items()
    }
    #: worst (shortest-window) burn rate per role, for the overload rule
    role_burn: dict[str, float] = {}
    for role, r in roles.items():
        for wd in ((r.get("slo") or {}).get("windows") or {}).values():
            burn = (wd or {}).get("burn_rate")
            if burn is not None:
                role_burn[role] = max(role_burn.get(role, 0.0), float(burn))

    #: fleet-wide handover fallback tally (storm rule below)
    handover_done = handover_fb = 0
    #: fleet-wide KV-economy migration tally (migration-storm rule below)
    migration_done = migration_fb = fleet_requests = 0

    for iid, w in sorted(workers.items()):
        age = float(w.get("last_seen_s") or 0.0)
        handover_done += int(w.get("handovers_total") or 0)
        handover_fb += int(w.get("handover_fallbacks_total") or 0)
        migration_done += int(w.get("kv_migrations_total") or 0)
        migration_fb += int(w.get("kv_migration_fallbacks_total") or 0)
        fleet_requests += int(w.get("requests_received") or 0)
        if str(w.get("state") or "") == "handover":
            # live KV migration (POST /v1/admin/handover / planner
            # scale-down / rolling upgrade): planned, suppress the
            # dead/stalled/skew rules like a drain. But EVERY phase is
            # deadline-bounded and any failure degrades to drain — a
            # handover that went silent past the dead threshold is stuck.
            wedged = age > DEAD_AFTER_S
            findings.append(_finding(
                "warning" if wedged else "info",
                "handover-stuck" if wedged else "handover-worker", iid,
                (f"{iid} is mid-handover but went silent "
                 f"(last_seen {age:.1f}s ago, phase="
                 f"{w.get('handover_phase') or '?'}) — the fallback-to-"
                 "drain path should have ended this"
                 if wedged else
                 f"{iid} is handing over (phase="
                 f"{w.get('handover_phase') or '?'}, "
                 f"{w.get('num_running') or 0} running)"),
                {"state": "handover", "last_seen_s": age,
                 "handover_phase": w.get("handover_phase"),
                 "num_running": w.get("num_running"),
                 "handover_bytes_total": w.get("handover_bytes_total")},
                ("check the worker's JSONL log for the stuck phase; if "
                 "the process is alive, SIGTERM it — the drain path "
                 "still exits 0 and streams replay on survivors"
                 if wedged else
                 "no action: KV pages are migrating to a successor; the "
                 "worker exits 0 when done (or falls back to a plain "
                 "drain on any failure)"),
            ))
            continue
        if str(w.get("state") or "") == "draining":
            # planned wind-down (SIGTERM / POST /v1/admin/drain): the
            # dead/stalled/skew rules below would misread a drain as an
            # outage — suppress them. But a drain is supposed to END
            # (budget default 30s, then exit 0 and the snapshot entry
            # ages out) — one that went SILENT past the dead threshold
            # is a wedged drain, which must still surface as a warning.
            # (stalls_total is lifetime-cumulative, so a pre-drain stall
            # must not read as a wedged drain — only silence does.)
            wedged = age > DEAD_AFTER_S
            findings.append(_finding(
                "warning" if wedged else "info", "draining-worker", iid,
                (f"{iid} is draining but looks wedged "
                 f"(last_seen {age:.1f}s ago) — the drain budget "
                 "should have ended this"
                 if wedged else
                 f"{iid} is draining (planned wind-down; "
                 f"{w.get('num_running') or 0} running)"),
                {"state": "draining", "last_seen_s": age,
                 "num_running": w.get("num_running"),
                 "stalls_total": w.get("stalls_total")},
                ("verify the process exited 0; if it is still alive "
                 "past its --drain-budget, read its /v1/debug/stalls "
                 "and JSONL log — in-flight work may be wedged"
                 if wedged else
                 "no action: the worker deregistered and is finishing "
                 "in-flight requests; it exits 0 when drained (or when "
                 "its --drain-budget lapses)"),
            ))
            continue
        if age > DEAD_AFTER_S:
            findings.append(_finding(
                "critical", "dead-worker", iid,
                f"{iid} stopped publishing {age:.1f}s ago",
                {"last_seen_s": age},
                "check the worker process / its fabric connection; "
                "deregister or restart it",
            ))
            continue  # stale numbers would double-diagnose below

        stalls = int(w.get("stalls_total") or 0)
        if stalls > 0:
            findings.append(_finding(
                "critical", "stalled-worker", iid,
                f"{iid} diagnosed {stalls} stalled stream(s) "
                f"({w.get('stalls_by_cause')})",
                {"stalls_total": stalls,
                 "stalls_by_cause": w.get("stalls_by_cause")},
                "read the watchdog diagnosis in the worker's JSONL log "
                "(thread stacks + flight window + trace ids); "
                "GET /v1/debug/stalls on the worker's process",
            ))

        recs = _flight_records(flight or {}, iid)
        if recs:
            n = len(recs)
            compile_steps = sum(1 for r in recs if r.get("compiles"))
            if n >= 8 and compile_steps / n > COMPILE_STORM_FRACTION:
                findings.append(_finding(
                    "warning", "compile-storm", iid,
                    f"{iid}: compile events in {compile_steps}/{n} of the "
                    "recent steps — the program family is churning",
                    {"compile_steps": compile_steps, "window": n},
                    "inspect GET /v1/debug/programs for the churning "
                    "kind; pin decode buckets / prefill chunking so "
                    "shapes stop multiplying",
                ))
            preempted = sum(r.get("preempted", 0) for r in recs)
            free = recs[-1].get("free_pages", None)
            total = int(w.get("kv_total_pages") or 0)
            if preempted > 0 or (
                free is not None and total
                and free <= total * POOL_FREE_FRACTION
            ):
                findings.append(_finding(
                    "warning", "pool-exhaustion", iid,
                    f"{iid}: page pool under pressure (free={free}, "
                    f"watermark={recs[-1].get('watermark')}, "
                    f"preemptions_in_window={preempted})",
                    {"free_pages": free, "preempted": preempted,
                     "watermark": recs[-1].get("watermark"),
                     "total_pages": total},
                    "grow --num-pages (or add workers / enable "
                    "--kv-quantize int8 for ~2x effective capacity); "
                    "preemption-by-recompute burns whole prompts",
                ))
            # prefill-induced decode stall: pure prefill dispatches while
            # decode rows exist and no mixed steps are being taken
            pure_prefill = sum(
                1 for r in recs
                if r.get("kind") == "prefill" and r.get("running", 0) > r.get("n_prefill", 0)
            )
            mixed_steps = sum(1 for r in recs if r.get("kind") == "mixed")
            if pure_prefill >= 3 and mixed_steps == 0:
                findings.append(_finding(
                    "warning", "decode-stall", iid,
                    f"{iid}: {pure_prefill} pure prefill steps ran while "
                    "decode rows waited and no mixed steps fired — "
                    "running requests pay the prefill drain as ITL",
                    {"pure_prefill_steps": pure_prefill,
                     "mixed_steps": mixed_steps, "window": n},
                    "enable mixed steps (drop --no-mixed-steps) or lower "
                    "the prefill budget; see docs/engine.md 'Mixed steps'",
                ))
        elif flight_collected and int(w.get("num_running") or 0) > 0:
            # only meaningful when flight data WAS collected for this
            # fleet — in --snapshot-only mode (no flight doc) a busy
            # worker with no records is the norm, not a wedge
            findings.append(_finding(
                "warning", "stalled-worker", iid,
                f"{iid}: {w.get('num_running')} running request(s) but no "
                "recent flight records — the engine loop may be wedged",
                {"num_running": w.get("num_running")},
                "check the worker's /v1/debug/stalls and JSONL log; a "
                "dispatch stuck in the device runtime shows in the "
                "engine thread's stack",
            ))

        # overload (docs/operations.md "Overload & draining"): two
        # mirror-image states — bounded admission actively shedding
        # (capacity is the fix), vs a deep unbounded queue silently
        # burning the SLO budget (admission caps are the fix)
        rejects = int(w.get("overload_rejects") or 0)
        waiting = int(w.get("num_waiting") or 0)
        running = int(w.get("num_running") or 0)
        burn = role_burn.get(str(w.get("role", "?")), 0.0)
        if rejects > 0:
            findings.append(_finding(
                "warning", "overload", iid,
                f"{iid}: bounded admission rejected {rejects} request(s) "
                f"(waiting={waiting}) — this worker is shedding",
                {"overload_rejects": rejects, "num_waiting": waiting,
                 "num_running": running,
                 "deadline_expired": w.get("deadline_expired")},
                "shedding is working as designed; raise capacity (add "
                "workers / grow the pool) if the 429 rate is above what "
                "clients tolerate — dynamo_tpu_shed_total{reason} at the "
                "frontend names the shed reasons",
            ))
        elif waiting > max(QUEUE_DEPTH_FLOOR, 4 * running) and burn > 1.0:
            findings.append(_finding(
                "warning", "overload", iid,
                f"{iid}: {waiting} requests queued against {running} "
                f"running while the role burns its SLO budget at "
                f"{burn:.1f}x, with ZERO admission rejects — the queue "
                "is unbounded",
                {"num_waiting": waiting, "num_running": running,
                 "burn_rate": burn, "overload_rejects": 0},
                "enable admission caps (--max-waiting on workers, "
                "--max-inflight at the frontend) so excess load answers "
                "429 + Retry-After instead of queueing past its deadline",
            ))

        # tier-pressure (docs/operations.md "The KV economy"): the HBM
        # pool is pegged at its demotion watermark AND the KVBM tier
        # traffic is dominated by DISK hits — the hot working set has
        # been demoted past the host slab, so every "warm" hit now pays
        # an NVMe promotion. More tiering can't fix that; HBM capacity
        # (or a higher demotion threshold) can.
        host_hits = int(w.get("kvbm_host_hits_total") or 0)
        disk_hits = int(w.get("kvbm_disk_hits_total") or 0)
        tier_hits = host_hits + disk_hits
        demotions = int(w.get("kvbm_demotions_total") or 0)
        free_pages = w.get("kv_free_pages")
        total_pages = int(w.get("kv_total_pages") or 0)
        hbm_pegged = (
            free_pages is not None and total_pages > 0
            and int(free_pages) <= total_pages * POOL_FREE_FRACTION
        )
        if (
            demotions > 0 and hbm_pegged and tier_hits >= TIER_HIT_FLOOR
            and disk_hits >= tier_hits * TIER_DISK_HIT_SHARE
        ):
            findings.append(_finding(
                "warning", "tier-pressure", iid,
                f"{iid}: HBM pool pegged ({free_pages}/{total_pages} "
                f"free) with {disk_hits}/{tier_hits} tiered KV hits "
                "served from DISK — the hot working set was demoted "
                "past host slab and warm hits now pay NVMe promotion",
                {"kv_free_pages": free_pages,
                 "kv_total_pages": total_pages,
                 "kvbm_demotions_total": demotions,
                 "kvbm_host_hits_total": host_hits,
                 "kvbm_disk_hits_total": disk_hits,
                 "kvbm_host_blocks": w.get("kvbm_host_blocks"),
                 "kvbm_disk_blocks": w.get("kvbm_disk_blocks")},
                "add HBM capacity (workers or --num-pages) or raise the "
                "demotion threshold so the hot set stays resident; the "
                "router already discounts disk-tier warmth, so persistent "
                "disk hits mean demand, not misrouting",
            ))

        mean = role_mean.get(str(w.get("role", "?")), 0.0)
        tok = float(w.get("tok_s") or 0.0)
        if mean > 1.0 and tok < mean * SKEW_FRACTION:
            findings.append(_finding(
                "warning", "skewed-worker", iid,
                f"{iid}: {tok:.1f} tok/s vs role mean {mean:.1f} — a "
                "limping replica drags the pool's SLA",
                {"tok_s": tok, "role_mean_tok_s": round(mean, 1)},
                "compare its flight window and /v1/debug/programs "
                "attainment against a healthy peer; drain + restart if "
                "the hardware is degraded",
            ))

    for role, r in sorted(roles.items()):
        slo = r.get("slo") or {}
        for win, wd in sorted((slo.get("windows") or {}).items()):
            burn = (wd or {}).get("burn_rate")
            if burn is not None and burn > 1.0:
                findings.append(_finding(
                    "warning", "sla-burn", None,
                    f"role {role}: burning error budget at {burn:.1f}x "
                    f"over the {win}s window "
                    f"(attainment {wd.get('attainment')})",
                    {"role": role, "window_s": win, "burn_rate": burn},
                    "scale the role up (planner/operator) or shed load; "
                    "fleet_top's BURN column names the worst workers",
                ))

    if handover_fb >= FALLBACK_STORM_COUNT and handover_fb > handover_done:
        findings.append(_finding(
            "warning", "handover-fallback-storm", None,
            f"{handover_fb} handover(s) degraded to plain drain vs "
            f"{handover_done} completed — upgrades are losing their "
            "warm-KV benefit fleet-wide",
            {"handover_fallbacks_total": handover_fb,
             "handovers_total": handover_done},
            "read the retiring workers' logs for the failing phase "
            "(extract / offer / transfer / adopt); common causes: "
            "successors with full pools, a partitioned transfer plane, "
            "or single-worker pools with no successor at all",
        ))

    # migration-storm: two failure signatures over the KV economy's
    # per-prefix migrations. (1) transfers keep DEGRADING — every
    # attempt falls back to cold prefill, so the fleet pays migration
    # overhead with none of the warm-TTFT benefit. (2) transfers
    # SUCCEED but fire on so large a share of requests that the same
    # hot prefixes must be ping-ponging between workers.
    if (
        migration_fb >= MIGRATION_FALLBACK_STORM_COUNT
        and migration_fb > migration_done
    ):
        findings.append(_finding(
            "warning", "migration-storm", None,
            f"{migration_fb} prefix migration(s) degraded to cold "
            f"prefill vs {migration_done} completed — the KV economy "
            "is paying transfer overhead with no warm-TTFT benefit",
            {"kv_migration_fallbacks_total": migration_fb,
             "kv_migrations_total": migration_done},
            "read the source workers' logs for the failing phase "
            "(extract / offer / transfer); common causes: destinations "
            "with full pools or a partitioned transfer plane — the "
            "router's backoff fences repeat attempts, but the break-even "
            "gate cannot see transport failures",
        ))
    elif (
        migration_done >= MIGRATION_CHURN_FLOOR
        and migration_done > fleet_requests * MIGRATION_CHURN_RATIO
    ):
        findings.append(_finding(
            "warning", "migration-storm", None,
            f"{migration_done} prefix migration(s) completed against "
            f"{fleet_requests} fleet request(s) — more than one "
            f"migration per {int(1 / MIGRATION_CHURN_RATIO)} requests "
            "means hot prefixes are ping-ponging between workers",
            {"kv_migrations_total": migration_done,
             "fleet_requests_received": fleet_requests,
             "kv_migration_fallbacks_total": migration_fb},
            "raise the router's migration backoff window and/or "
            "DYN_KV_ECONOMY_MIN_FLOPS_PER_BYTE so only clearly "
            "profitable moves clear the break-even gate; see "
            "docs/operations.md 'The KV economy'",
        ))

    findings.extend(_kv_index_rules((fleet or {}).get("kv_index")))
    findings.extend(_planner_rules((fleet or {}).get("planner")))
    findings.extend(_trace_rules(traces, workers))
    findings.extend(_host_skew_rules(workers))
    findings.extend(_perf_regression_rules(ledger))

    order = {"critical": 0, "warning": 1, "info": 2}
    findings.sort(key=lambda f: (order.get(f["severity"], 9), str(f["worker"])))
    return findings


def _host_skew_rules(workers: dict) -> list[dict]:
    """host-skew: under multi-host SPMD every lockstep dispatch runs at
    the SLOWEST host's pace — group the live workers' flight-window
    dispatch p95 by their `host` (jax.process_index()) and name the
    straggler. Needs >= 2 hosts reporting; single-host fleets (and
    workers without the HBM/mesh plane) never fire it."""
    by_host: dict[str, float] = {}
    members: dict[str, list[str]] = {}
    for iid, w in sorted(workers.items()):
        p95 = w.get("dispatch_p95_ms")
        if not isinstance(p95, (int, float)):
            continue
        if float(w.get("last_seen_s") or 0.0) > DEAD_AFTER_S:
            continue  # the dead-worker rule owns stale frames
        h = str(int(w.get("host") or 0))
        by_host[h] = max(by_host.get(h, 0.0), float(p95))
        members.setdefault(h, []).append(iid)
    if len(by_host) < 2:
        return []
    fastest = min(by_host.values())
    out: list[dict] = []
    for h, p95 in sorted(by_host.items()):
        if p95 < HOST_SKEW_FLOOR_MS:
            continue
        if fastest > 0 and p95 > fastest * HOST_SKEW_RATIO:
            out.append(_finding(
                "warning", "host-skew", None,
                f"host {h} dispatches at p95 {p95:.1f}ms vs the fastest "
                f"host's {fastest:.1f}ms ({p95 / fastest:.1f}x) — under "
                "lockstep SPMD every dispatch waits for it",
                {"host": h, "dispatch_p95_ms": p95,
                 "fastest_host_p95_ms": fastest,
                 "workers": members.get(h, [])},
                "compare GET /v1/debug/mesh dispatch sections across "
                "hosts; look for thermal throttling, a noisy neighbor, "
                "or host-side input work pinned to that process "
                "(docs/observability.md 'Reading the perf plane')",
            ))
    return out


def _import_perf_ledger():
    """Lazy import of dynamo_tpu.telemetry.perf_ledger — the doctor
    stays dependency-free unless the ledger plane is actually used.
    Running as `python scripts/doctor.py` puts scripts/ (not the repo
    root) on sys.path, so fall back to the parent directory."""
    try:
        from dynamo_tpu.telemetry import perf_ledger
    except ImportError:
        import os

        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        try:
            from dynamo_tpu.telemetry import perf_ledger
        except ImportError:
            return None
    return perf_ledger


def _perf_regression_rules(ledger: Optional[list]) -> list[dict]:
    """perf-regression: compare each round's latest ledger row against
    the previous ok row with the SAME config fingerprint (same
    workload), using the shared tolerance bands. Fires one warning per
    regressed comparison — the doctor flags drift; scripts/perf_diff.py
    is the CI gate."""
    if not ledger:
        return []
    perf_ledger = _import_perf_ledger()
    if perf_ledger is None:
        return []
    by_round = perf_ledger.rows_by_round(ledger)
    ordered = [r for r in by_round.values() if r["ok"]]
    out: list[dict] = []
    for prev, cur in zip(ordered, ordered[1:]):
        if prev.get("fingerprint") != cur.get("fingerprint"):
            continue
        result = perf_ledger.compare_rows(prev, cur)
        if not result["regressions"]:
            continue
        worst = max(
            (r for r in result["rows"] if r["verdict"] == "REGRESSION"),
            key=lambda r: abs(r["rel"] or 0.0),
        )
        out.append(_finding(
            "warning", "perf-regression", None,
            f"round {cur['round']} regressed "
            f"{', '.join(result['regressions'])} vs {prev['round']} "
            f"(worst: {worst['metric']} {worst['rel']:+.1%}, band "
            f"{worst['band']:.0%})",
            {"round_a": prev["round"], "round_b": cur["round"],
             "fingerprint": cur.get("fingerprint"),
             "regressions": result["regressions"],
             "rows": [r for r in result["rows"]
                      if r["verdict"] == "REGRESSION"]},
            "rerun the round to rule out noise, then bisect: "
            f"`python scripts/perf_diff.py {prev['round']} "
            f"{cur['round']}` shows the full table "
            "(docs/observability.md 'Reading the perf plane')",
        ))
    return out


def _control_plane_rules(fleet: dict, workers: dict) -> list[dict]:
    """control-plane-degraded + replication-lag over the /v1/fleet
    `control_plane` section (docs/operations.md "Control-plane HA")."""
    out: list[dict] = []
    cp = (fleet or {}).get("control_plane") or {}
    if cp.get("degraded"):
        ages = [
            float(w.get("last_seen_s") or 0.0) for w in workers.values()
        ]
        all_stale = not ages or all(a > DEAD_AFTER_S for a in ages)
        out.append(_finding(
            "critical" if all_stale else "warning",
            "control-plane-degraded", None,
            (
                "the metrics service cannot reach any broker "
                f"({cp.get('disconnected_s', 0)}s) and every worker's "
                "frames are stale — the WHOLE fleet is in broker-less "
                "degraded mode (serving from cached discovery, KV "
                "scores stale-cold, planner holding)"
                if all_stale else
                "the metrics service lost its broker "
                f"({cp.get('disconnected_s', 0)}s); worker frames are "
                "still fresh, so this may be a partial partition"
            ),
            {"disconnected_s": cp.get("disconnected_s"),
             "addresses": cp.get("addresses"),
             "degraded_total": cp.get("degraded_total"),
             "workers_stale": all_stale},
            "restart/restore a broker (or promote the standby: `run "
            "fabric --promote <standby>`); chats keep serving over "
            "direct ingress meanwhile, and KV indexes resync on "
            "reconnect",
        ))
    for iid, w in sorted(workers.items()):
        if int(w.get("degraded") or 0) and float(
            w.get("last_seen_s") or 0.0
        ) <= DEAD_AFTER_S:
            out.append(_finding(
                "warning", "control-plane-degraded", iid,
                f"{iid} reports broker-less degraded mode "
                f"(dropped {w.get('kv_events_dropped_total') or 0} KV "
                f"event(s), {w.get('kv_events_pending') or 0} pending)",
                {"degraded": 1,
                 "degraded_entries_total": w.get("degraded_entries_total"),
                 "kv_events_dropped_total":
                     w.get("kv_events_dropped_total"),
                 "kv_events_pending": w.get("kv_events_pending")},
                "this worker cannot reach the broker others can — check "
                "its --fabric list and the network path; its KV events "
                "buffer (bounded) and the index resyncs on reconnect",
            ))
    broker = cp.get("broker") or {}
    lag = int(broker.get("repl_lag_records") or 0)
    if int(broker.get("repl_subscribers") or 0) > 0 and (
        lag > REPL_LAG_WARN_RECORDS
    ):
        out.append(_finding(
            "warning", "replication-lag", None,
            f"the warm standby trails the primary's journal by {lag} "
            f"records — promoting now would LOSE that tail",
            {"repl_lag_records": lag,
             "repl_subscribers": broker.get("repl_subscribers"),
             "fence": broker.get("fence")},
            "hold any manual failover; check the standby host/link "
            "(fabric repl_lag_records should sit near 0) — the detector "
            "still promotes on primary death, accepting the gap "
            "(sequencing consumers resync)",
        ))
    return out


def _trace_rules(traces: Optional[dict], workers: dict) -> list[dict]:
    """slow-trace-attribution: attribute each of the N worst KEPT traces
    (the fleet trace plane's GET /v1/traces, tail-sampled so anomalies
    are all there) to its dominant breakdown phase; actionable dominant
    phases fold into one finding per phase, naming the traces and — when
    the traces agree on a pool — the role to act on."""
    findings: list[dict] = []
    if not isinstance(traces, dict):
        return findings
    kept = [t for t in traces.get("traces") or [] if isinstance(t, dict)]
    kept.sort(
        key=lambda t: float(t.get("duration_ms") or 0.0), reverse=True
    )
    by_phase: dict[str, list[dict]] = {}
    for t in kept[:TRACE_WORST_N]:
        bd = t.get("breakdown") or {}
        total = float(bd.get("total_ms") or 0.0)
        dominant = bd.get("dominant")
        if not dominant or total < TRACE_MIN_TOTAL_MS:
            continue
        share = float((bd.get("phases") or {}).get(dominant) or 0.0) / total
        if share < TRACE_DOMINANT_SHARE:
            continue
        if dominant in TRACE_PHASE_ACTIONS:
            by_phase.setdefault(dominant, []).append(t)
    for phase, ts in sorted(by_phase.items()):
        roles = {
            str((workers.get(w) or {}).get("role"))
            for t in ts
            for w in t.get("workers") or ()
            if w in workers
        } - {"None"}
        pool = (
            f" on the {next(iter(roles))} pool" if len(roles) == 1 else ""
        )
        worst = ts[0]
        findings.append(_finding(
            "warning", "slow-trace-attribution", None,
            f"{len(ts)} of the {min(TRACE_WORST_N, len(kept))} worst "
            f"kept traces are dominated by {phase}{pool} (worst: "
            f"{worst.get('trace_id')} at "
            f"{float(worst.get('duration_ms') or 0):.0f} ms, "
            f"{float((worst.get('breakdown') or {}).get('phases', {}).get(phase) or 0):.0f} ms "
            f"in {phase})",
            {"phase": phase, "roles": sorted(roles),
             "traces": [
                 {"trace_id": t.get("trace_id"),
                  "duration_ms": t.get("duration_ms"),
                  "kept_reasons": t.get("kept_reasons"),
                  "breakdown": (t.get("breakdown") or {}).get("phases")}
                 for t in ts
             ]},
            TRACE_PHASE_ACTIONS[phase],
        ))
    return findings


def _kv_index_rules(kv_index: Optional[dict]) -> list[dict]:
    """KV index consistency (fleet snapshot `kv_index` section,
    published by KV-aware routers over kv_index.status — docs/
    operations.md "KV index consistency"). Drift that is detected AND
    repaired is an info note (the plane converged); subtrees sitting
    stale are a warning (those workers route cold — real prefix hits
    are being recomputed); stale subtrees with FAILING resyncs are
    critical when repair has never succeeded (the index cannot
    converge: snapshot fetches are failing or sequencing is off)."""
    findings: list[dict] = []
    if not isinstance(kv_index, dict):
        return findings
    stale = int(kv_index.get("stale_workers") or 0)
    gaps = int(kv_index.get("gaps_total") or 0)
    mismatches = int(kv_index.get("digest_mismatches_total") or 0)
    resyncs = int(kv_index.get("resyncs_total") or 0)
    failures = int(kv_index.get("resync_failures_total") or 0)
    drift = int(kv_index.get("drift_blocks_total") or 0)
    evidence = {
        "stale_workers": stale, "gaps_total": gaps,
        "digest_mismatches_total": mismatches,
        "resyncs_total": resyncs, "resync_failures_total": failures,
        "drift_blocks_total": drift,
    }
    if stale > 0:
        wedged = failures > 0 and resyncs == 0
        findings.append(_finding(
            "critical" if wedged else "warning", "kv-index-drift", None,
            (f"{stale} index subtree(s) stale and every resync attempt "
             f"has failed ({failures} failure(s), 0 succeeded) — the "
             "prefix index cannot converge"
             if wedged else
             f"{stale} index subtree(s) stale — prefix routing scores "
             "those workers COLD until their resync lands (warm hits on "
             "them are being recomputed)"),
            evidence,
            ("check that workers run with KV sequencing enabled (no "
             "--no-kv-sequencing) and that the router can reach their "
             "ingress for kv.snapshot; a dead worker clears when its "
             "registration prunes"
             if wedged else
             "usually self-heals within an anti-entropy sweep; if stale "
             "persists, check the worker's ingress reachability and the "
             "router log's kv.snapshot fetch errors"),
        ))
    elif gaps or mismatches:
        findings.append(_finding(
            "info", "kv-index-drift", None,
            f"index drift was detected ({gaps} sequence gap(s), "
            f"{mismatches} digest mismatch(es)) and repaired by "
            f"{resyncs} resync(s), {drift} block(s) corrected — the "
            "event plane is lossy but converging",
            evidence,
            "no action needed now; a climbing gap rate means KV events "
            "are being dropped (fabric outages, ring overflow) — check "
            "dynamo_tpu_kv_index_gaps_total's rate and the fabric's "
            "health",
        ))
    return findings


def _planner_rules(planner: Optional[dict]) -> list[dict]:
    """Closed-loop planner health (fleet snapshot `planner` section,
    published by ControlRunner through the metrics service)."""
    findings: list[dict] = []
    if not isinstance(planner, dict):
        return findings
    setpoint = planner.get("setpoint") or {}
    cooldown = float(setpoint.get("cooldown_s") or 0.0)
    flip_cooldown = float(setpoint.get("flip_cooldown_s") or 0.0)
    osc_window = (
        cooldown * OSCILLATION_WINDOW_FACTOR
        if cooldown > 0.0
        else OSCILLATION_WINDOW_FLOOR_S
    )
    flip_window = (
        flip_cooldown * OSCILLATION_WINDOW_FACTOR
        if flip_cooldown > 0.0
        else OSCILLATION_WINDOW_FLOOR_S
    )
    recent = [
        d for d in (planner.get("recent_decisions") or [])
        if isinstance(d, dict)
    ]

    # planner-oscillation: alternating scale directions on one role
    # inside the oscillation window (a small multiple of the enforced
    # cooldown — see OSCILLATION_WINDOW_FACTOR) — the loop is chasing
    # its own wake
    by_role: dict = {}
    for d in sorted(recent, key=lambda d: float(d.get("ts") or 0.0)):
        if d.get("action") in ("scale_up", "scale_down") and d.get("role"):
            by_role.setdefault(str(d["role"]), []).append(d)
    for role, ds in sorted(by_role.items()):
        reversals = 0
        for a, b in zip(ds, ds[1:]):
            dt = float(b.get("ts") or 0.0) - float(a.get("ts") or 0.0)
            if a["action"] != b["action"] and dt < osc_window:
                reversals += 1
        if reversals >= OSCILLATION_REVERSALS:
            findings.append(_finding(
                "warning", "planner-oscillation", None,
                f"planner reversed scale direction on {role} {reversals} "
                f"time(s) within the {osc_window:.0f}s oscillation "
                "window — the control loop is flapping",
                {"role": role, "reversals": reversals,
                 "cooldown_s": cooldown, "window_s": osc_window,
                 "decisions": ds[-6:]},
                "widen the hysteresis band (burn_low/burn_high) or raise "
                "--cooldown; a loop that spawns then drains the same "
                "worker burns engine cold-starts for nothing",
            ))
    flips = [
        d for d in sorted(recent, key=lambda d: float(d.get("ts") or 0.0))
        if d.get("action") == "flip"
    ]
    # a storm is ALTERNATION (A->B then B->A — the same capacity bounced
    # back), not a same-direction flip train, which is a legitimate ramp
    # (e.g. flipping several idle prefill workers into a flash crowd)
    storm = sum(
        1
        for a, b in zip(flips, flips[1:])
        if (
            float(b.get("ts") or 0.0) - float(a.get("ts") or 0.0)
            < flip_window
            and (a.get("src"), a.get("dst")) == (b.get("dst"), b.get("src"))
        )
    )
    if storm >= FLIP_STORM_COUNT:
        findings.append(_finding(
            "warning", "planner-oscillation", None,
            f"{len(flips)} role flips with {storm} pair(s) inside the "
            f"{flip_window:.0f}s flip oscillation window — a flip storm "
            "thrashes pool roles (each flip drains a worker)",
            {"flips": len(flips), "storm_pairs": storm,
             "flip_cooldown_s": flip_cooldown,
             "window_s": flip_window},
            "raise --flip-cooldown or disable --flip until the pressure "
            "signals stop alternating between the pools",
        ))

    # sla-unrecovered: scaled to the ceiling, still burning
    burn_ticks = int(planner.get("burn_high_ticks") or 0)
    if burn_ticks >= BURN_UNRECOVERED_TICKS and planner.get("at_max"):
        signals = planner.get("signals") or {}
        limits = planner.get("limits") or {}
        findings.append(_finding(
            "critical", "sla-unrecovered", None,
            f"fleet has burned its SLO budget for {burn_ticks} "
            f"consecutive planner ticks with the decode pool pinned at "
            f"max_decode={limits.get('max_decode')} — the control loop "
            "is out of headroom",
            {"burn_high_ticks": burn_ticks,
             "burn_rate": signals.get("burn_rate"),
             "sla_attainment": signals.get("sla_attainment"),
             "limits": limits},
            "raise --max-decode (add capacity) or shed load "
            "(--shed-burn-threshold / --max-inflight); the planner "
            "cannot recover this SLA by itself",
        ))
    return findings


def render_report(fleet: dict, findings: list[dict]) -> str:
    """Findings -> the human-readable report."""
    n_workers = len((fleet or {}).get("workers") or {})
    out = [f"dynamo-tpu doctor: {n_workers} worker(s), "
           f"{len(findings)} finding(s)"]
    if not findings:
        out.append("  all clear: no rule fired")
        return "\n".join(out)
    for f in findings:
        head = f"[{f['severity'].upper():8}] {f['rule']}"
        if f["worker"]:
            head += f" @ {f['worker']}"
        out.append(head)
        out.append(f"  {f['summary']}")
        out.append(f"  -> {f['action']}")
    return "\n".join(out)


def _fetch(url: str, path: str) -> Optional[dict]:
    try:
        with urllib.request.urlopen(f"{url}{path}", timeout=5) as resp:
            return json.loads(resp.read().decode())
    except Exception as e:
        print(f"fetch {url}{path} failed: {e}", file=sys.stderr)
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--url", default="http://127.0.0.1:9091",
        help="metrics service base URL",
    )
    ap.add_argument(
        "--snapshot", default=None,
        help="recorded /v1/fleet JSON file instead of fetching",
    )
    ap.add_argument(
        "--flight", default=None,
        help="recorded /v1/debug/flight JSON file instead of fetching",
    )
    ap.add_argument(
        "--traces", default=None,
        help="recorded /v1/traces JSON file instead of fetching",
    )
    ap.add_argument(
        "--ledger", default=None,
        help="perf ledger (artifacts/perf_ledger.jsonl) for the "
             "perf-regression rule; never fetched",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="emit the findings as JSON instead of the text report",
    )
    args = ap.parse_args(argv)

    def load(path):
        with open(path) as f:
            return json.load(f)

    fleet = load(args.snapshot) if args.snapshot else _fetch(args.url, "/v1/fleet")
    if fleet is None:
        return 1
    flight = (
        load(args.flight) if args.flight
        else (_fetch(args.url, "/v1/debug/flight") if not args.snapshot else {})
    )
    traces = (
        load(args.traces) if args.traces
        else (
            _fetch(
                args.url,
                f"/v1/traces?sort=duration&limit={2 * TRACE_WORST_N}",
            )
            if not args.snapshot
            else {}
        )
    )
    ledger_rows = None
    if args.ledger:
        perf_ledger = _import_perf_ledger()
        if perf_ledger is None:
            print("ledger: dynamo_tpu.telemetry.perf_ledger not "
                  "importable", file=sys.stderr)
        else:
            try:
                ledger_rows, skipped = perf_ledger.read_rows(args.ledger)
                for p in skipped:
                    print(f"ledger: skipped {p}", file=sys.stderr)
            except OSError as e:
                print(f"ledger {args.ledger} unreadable: {e}",
                      file=sys.stderr)
    findings = diagnose(fleet, flight or {}, traces or {}, ledger_rows)
    if args.json:
        print(json.dumps(findings, indent=2))
    else:
        print(render_report(fleet, findings))
    return 2 if any(f["severity"] == "critical" for f in findings) else 0


if __name__ == "__main__":
    sys.exit(main())
