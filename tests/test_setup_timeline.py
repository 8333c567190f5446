"""Set-up told from inside (engine/engine.py): the constructor times
itself in phases (`engine.boot*`, the `boot_*` counters) and every step
program's first call is split by jax's own events into trace, lowering
and XLA-or-cache (`_FirstCall`; `engine.programs`, the `compile_*`
counters, the `engine.compile` span's args). CPU, the `tiny` preset, a
compile cache of its own."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu import platform, telemetry
from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.engine import JaxEngine, _FirstCall
from dynamo_tpu.engine.request import SamplingParams
from test_engine_spans import engine_spans

BOOT_COUNTERS = ("boot_before_ms", "boot_ms", "boot_weights_ms",
                 "boot_pools_ms")
PARTS = ("trace_ms", "lower_ms", "backend_ms", "run_ms", "cache")
COMPILE_COUNTERS = ("compile_ms", "compile_trace_ms", "compile_lower_ms",
                    "compile_backend_ms", "compile_cache_requests",
                    "compile_cache_hits", "compiles")


def generate(eng: JaxEngine, rid: str) -> None:
    eng.add_request(rid, [5, 17, 42, 9, 3],
                    SamplingParams(max_tokens=9, ignore_eos=True))
    eng.run_to_completion()


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    """Two engines of one configuration built one after the other in this
    process over ONE compile cache that starts empty and keeps every
    program, each after a short generation, the second inside a profiler
    capture with the trace ring on: {first, second, around (perf_counter
    before and after the first constructor), spans (the capture's
    `engine.*`), ring}."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_dir = str(tmp_path_factory.mktemp("compile_cache"))
    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}

    def cache_everything() -> str:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        return cache_dir

    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    mp.setattr(platform, "enable_persistent_compile_cache", cache_everything)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    cc.reset_cache()
    telemetry.configure(enabled=True, ring_size=64)
    telemetry.reset()
    try:
        t_before = time.perf_counter()
        first = JaxEngine(EngineConfig.for_tests())
        t_after = time.perf_counter()
        generate(first, "a")
        trace_dir = str(tmp_path_factory.mktemp("boot_capture"))
        jax.profiler.start_trace(trace_dir)
        try:
            second = JaxEngine(EngineConfig.for_tests())
            generate(second, "b")
        finally:
            jax.profiler.stop_trace()
        spans = engine_spans(trace_dir)
        ring = [s for t in telemetry.list_traces(64)
                for s in telemetry.get_trace(t["trace_id"]) or []]
    finally:
        telemetry.configure(enabled=False)
        mp.undo()
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
    return {"first": first, "second": second, "around": (t_before, t_after),
            "spans": spans, "ring": ring}


# -- the boot ---------------------------------------------------------------


@pytest.mark.parametrize("counter", BOOT_COUNTERS)
def test_the_boot_counters_are_set(boots, counter):
    for eng in (boots["first"], boots["second"]):
        assert getattr(eng.metrics, counter) > 0, counter
        assert counter in eng.metrics.to_dict()


def test_weights_and_pools_lie_inside_the_boot(boots):
    m = boots["first"].metrics
    assert m.boot_weights_ms + m.boot_pools_ms <= m.boot_ms


def test_boot_end_is_on_the_callers_clock(boots):
    before, after = boots["around"]
    m = boots["first"].metrics
    assert before < m.boot_end_perf_s <= after
    # the whole constructor lies between the two reads as well
    assert m.boot_ms <= (after - before) * 1e3


def test_before_the_boot_is_the_age_of_the_process(boots):
    """Seconds, not epochs: older than the package's import, younger
    than the machine."""
    import dynamo_tpu

    age_s = platform.process_age_s()
    assert age_s >= time.perf_counter() - dynamo_tpu.IMPORTED_PERF_S - 0.05
    assert age_s <= time.clock_gettime(time.CLOCK_BOOTTIME)
    first, second = boots["first"].metrics, boots["second"].metrics
    assert first.boot_before_ms < second.boot_before_ms <= age_s * 1e3


def test_the_process_age_falls_back_to_the_import(monkeypatch):
    import builtins

    import dynamo_tpu

    def no_proc(path, *a, **kw):
        raise OSError(path)

    monkeypatch.setattr(builtins, "open", no_proc)
    age_s = platform.process_age_s()
    monkeypatch.undo()
    since_import = time.perf_counter() - dynamo_tpu.IMPORTED_PERF_S
    assert 0 < age_s <= since_import


@pytest.mark.parametrize("span", ["engine.boot", "engine.boot.weights",
                                  "engine.boot.pools"])
def test_the_boot_is_on_the_profilers_clock(boots, span):
    """A capture begun before the engine is built shows the boot, the
    phases inside the whole, each as long as its counter says."""
    spans = boots["spans"]
    mine = [e for e in spans if e["name"] == span]
    assert len(mine) == 1
    whole = next(e for e in spans if e["name"] == "engine.boot")
    assert whole["start"] <= mine[0]["start"]
    assert mine[0]["end"] <= whole["end"]
    counter = {"engine.boot": "boot_ms",
               "engine.boot.weights": "boot_weights_ms",
               "engine.boot.pools": "boot_pools_ms"}[span]
    assert mine[0]["ms"] == pytest.approx(
        getattr(boots["second"].metrics, counter), abs=1.0)


def test_programs_report_tells_the_boot(boots):
    eng = boots["first"]
    m = eng.metrics
    assert eng.programs_report()["boot"] == {
        "before_ms": round(m.boot_before_ms, 3),
        "weights_ms": round(m.boot_weights_ms, 3),
        "pools_ms": round(m.boot_pools_ms, 3),
        "ms": round(m.boot_ms, 3),
    }


# -- a first call, in its parts -----------------------------------------------


@pytest.mark.parametrize("part", PARTS)
def test_every_program_has_its_parts(boots, part):
    for eng in (boots["first"], boots["second"]):
        assert len(eng.programs) >= 2
        for p in eng.programs.values():
            assert part in p, p
            if part == "cache":
                assert p[part] in ("hit", "miss", "off")
            elif part != "run_ms":  # a remainder may round under zero
                assert p[part] >= 0.0
            assert {"kind", "key", "compile_ms"} <= set(p)


def test_the_parts_lie_inside_the_first_call(boots):
    for eng in (boots["first"], boots["second"]):
        for p in eng.programs.values():
            known = p["trace_ms"] + p["lower_ms"] + p["backend_ms"]
            assert 0 < known <= p["compile_ms"] + 1, p
            # the remainder is the rest of the span's own time
            assert known + p["run_ms"] <= p["compile_ms"] + 1e-2, p
            assert known + p["run_ms"] == pytest.approx(
                p["compile_ms"], abs=1.0)


def test_the_counters_are_the_sums_of_the_table(boots):
    for eng in (boots["first"], boots["second"]):
        m, table = eng.metrics, list(eng.programs.values())
        assert m.compiles == len(table)
        for counter, part in (("compile_trace_ms", "trace_ms"),
                              ("compile_lower_ms", "lower_ms"),
                              ("compile_backend_ms", "backend_ms")):
            assert getattr(m, counter) == pytest.approx(
                sum(p[part] for p in table), abs=0.01 * len(table))
        assert m.compile_cache_requests == sum(
            p["cache"] != "off" for p in table)
        assert m.compile_cache_hits == sum(
            p["cache"] == "hit" for p in table)


def test_the_first_engine_missed_the_cache(boots):
    eng = boots["first"]
    assert [p["cache"] for p in eng.programs.values()] == (
        ["miss"] * len(eng.programs))
    assert eng.metrics.compile_cache_hits == 0
    assert eng.metrics.compile_cache_requests == len(eng.programs)


def test_the_second_engine_hit_it(boots):
    """The same programs again, from new function objects: jax traces
    and lowers them again and XLA's part is a read of the cache."""
    first, second = boots["first"], boots["second"]
    assert set(second.programs) == set(first.programs)
    assert [p["cache"] for p in second.programs.values()] == (
        ["hit"] * len(second.programs))
    assert second.metrics.compile_cache_hits == len(second.programs)
    assert second.metrics.compile_cache_requests == len(second.programs)
    for key, p in second.programs.items():
        assert p["trace_ms"] > 0 and p["lower_ms"] > 0
        assert p["backend_ms"] < first.programs[key]["backend_ms"]


def test_kinds_carry_the_sums(boots):
    eng = boots["second"]
    rep = eng.programs_report()
    for kind, k in rep["kinds"].items():
        mine = [p for p in rep["programs"] if p["kind"] == kind]
        for part in ("trace_ms", "lower_ms", "backend_ms"):
            assert k[part] == pytest.approx(
                sum(p[part] for p in mine), abs=1e-2)
        assert k["cache_hits"] == len(mine)
    assert all(k["cache_hits"] == 0
               for k in boots["first"].programs_report()["kinds"].values())


def test_the_compile_span_carries_the_parts(boots):
    spans = [e for e in boots["spans"]
             if e["name"] == "engine.compile"]
    table = {p["key"]: p for p in boots["second"].programs.values()}
    assert len(spans) == len(table) >= 2
    for e in spans:
        p = table[e["key"]]
        assert e["cache"] == p["cache"] == "hit"
        for part in ("trace_ms", "lower_ms", "backend_ms", "run_ms"):
            assert float(e[part]) == pytest.approx(p[part], abs=1e-3)


def test_the_trace_ring_holds_no_compile_span(boots):
    """One stretch, one span: the profiler's. The ring was on while both
    engines compiled."""
    assert telemetry.enabled() is False  # the fixture turned it off again
    assert not [s for s in boots["ring"] if s["name"] == "engine.compile"]


# -- the accumulator and the listeners ----------------------------------------


def _slow_to_trace(ms: float):
    def body(x):
        time.sleep(ms / 1e3)  # runs while jax traces, never on a device
        return x * 2.0 + 1.0
    return body


def test_an_inner_jit_does_not_double_the_trace():
    """Trace events nest: the inner function's own event lies inside the
    outer's, which is fired last and holds it."""
    seen = []

    def on_duration(event, secs, **_kw):
        if event == engine_mod._TRACE_EVENT:
            seen.append(secs * 1e3)

    inner = jax.jit(_slow_to_trace(60.0))

    @jax.jit
    def outer(x):
        return inner(x) - 3.0

    x = jnp.ones((4,), jnp.float32)  # a program of its own, made outside
    engine_mod._listen_to_jax()
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        with _FirstCall() as call:
            outer(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    slow = [ms for ms in seen if ms >= 60.0]
    assert len(slow) >= 2, seen  # the inner event and the outer around it
    assert sum(seen) >= 120.0
    assert call.trace_ms == pytest.approx(max(seen), abs=1e-6)
    assert 60.0 <= call.trace_ms < 110.0
    assert call.lower_ms > 0 and call.backend_ms > 0


@pytest.mark.parametrize("events,want", [
    # thirty inner events of nothing and the outer that holds them
    ([("t", 0.0)] * 30 + [("t", 17.8), ("l", 5.0), ("b", 40.0)],
     (17.8, 5.0, 40.0)),
    # a kernel that lowers its own module inside the outer lowering, and
    # a lowering rule that traces a helper of its own
    ([("t", 9.0), ("l", 2.0), ("t", 0.5), ("l", 6.0), ("b", 1.0)],
     (9.0, 6.0, 1.0)),
    # a program traced, lowered and compiled twice adds two maxima
    ([("t", 1.0), ("t", 4.0), ("l", 2.0), ("b", 3.0),
      ("t", 2.0), ("t", 5.0), ("l", 1.0), ("b", 7.0)], (9.0, 3.0, 10.0)),
    # a trace that no lowering followed is still counted at the close
    ([("t", 3.0)], (3.0, 0.0, 0.0)),
])
def test_the_nesting_rule(events, want):
    names = {"t": engine_mod._TRACE_EVENT, "l": engine_mod._LOWER_EVENT,
             "b": engine_mod._BACKEND_EVENT}
    with _FirstCall() as call:
        for kind, ms in events:
            call.duration(names[kind], ms)
    got = (call.trace_ms, call.lower_ms, call.backend_ms)
    assert got == pytest.approx(want)
    parts = call.parts(100.0)
    assert parts["run_ms"] == pytest.approx(100.0 - sum(want))
    assert parts["cache"] == "off"


@pytest.mark.parametrize("hits,misses,want", [
    (0, 0, "off"), (0, 1, "miss"), (1, 0, "hit"), (1, 1, "miss"),
])
def test_cache_says_what_the_cache_holds_of_the_call(hits, misses, want):
    """A hit was answered, a miss compiled and written (a later start
    hits); a compile the cache was not asked for, or one too quick for
    jax to keep, is neither."""
    call = _FirstCall()
    call.hits, call.misses = hits, misses
    assert call.cache == want


def test_a_compile_too_quick_to_keep_is_off(boots, tmp_path):
    """jax writes no entry for a compile under
    `jax_persistent_cache_min_compile_time_secs`, so it can never hit:
    counted as a request it would keep a warm start under 100 %."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    x = jnp.ones((5,), jnp.float32)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)
    cc.reset_cache()
    try:
        with _FirstCall() as call:
            jax.jit(lambda v: v * 7.5 - 1.5)(x).block_until_ready()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert call.backend_ms > 0
    assert (call.hits, call.misses, call.cache) == (0, 0, "off")


def _compile_something(salt: float):
    jax.jit(lambda x: x * salt + salt)(
        jnp.ones((3,), jnp.float32)).block_until_ready()


@pytest.mark.parametrize("where", ["another_thread", "outside_a_first_call"])
def test_a_compile_elsewhere_moves_no_counter(boots, where):
    """The listeners add only into the calling thread's open first call:
    a program compiled on another thread while one is open here, or on
    this thread with none open, is attributed to nothing."""
    eng = boots["second"]
    before = {k: getattr(eng.metrics, k) for k in COMPILE_COUNTERS}
    table = {k: dict(p) for k, p in eng.programs.items()}
    if where == "another_thread":
        with _FirstCall() as call:
            t = threading.Thread(target=_compile_something, args=(1.25,))
            t.start()
            t.join()
        assert (call.trace_ms, call.lower_ms, call.backend_ms,
                call.hits, call.misses) == (0.0, 0.0, 0.0, 0, 0)
    else:
        assert getattr(engine_mod._first_calls, "open", None) is None
        _compile_something(2.5)
    assert {k: getattr(eng.metrics, k) for k in COMPILE_COUNTERS} == before
    assert eng.programs == table


def test_one_pair_of_listeners_a_process(boots):
    """Two engines were built: the pair was registered once."""
    from jax._src import monitoring

    durations = monitoring.get_event_duration_listeners()
    events = monitoring.get_event_listeners()
    assert durations.count(engine_mod._on_jax_duration) == 1
    assert events.count(engine_mod._on_jax_event) == 1
