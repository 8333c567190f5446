"""The ten readers of set-up (PR 54): each on a hand-made `ctx` with
hand-computed values, a float and never None where a denominator is 0,
None (and no raise) on the counters of an engine that does not time its
boot; the five parts of a run's set-up sum to `t0` less the process's
start; the ten entries as BENCHMARK.json holds them."""
import json

import pytest

from chipbench import manifest

LAYER = "set-up (cli/run.py, engine/engine.py __init__ and _cache_jit)"
#: name -> (unit, better), in the manifest's order
TEN = {
    "setup_before_engine_s": ("s", "lower"),
    "setup_weights_s": ("s", "lower"),
    "setup_pools_s": ("s", "lower"),
    "setup_first_calls_s": ("s", "lower"),
    "setup_ramp_rest_s": ("s", "lower"),
    "programs_before_window": ("programs", "lower"),
    "first_call_trace_s": ("s", "lower"),
    "first_call_lower_s": ("s", "lower"),
    "first_call_backend_s": ("s", "lower"),
    "compile_cache_hit_share": ("%", "higher"),
}
#: sha256 of the 89 accepted entries as JSON (sorted keys), on the parent
ACCEPTED_SHA256 = (
    "7b0d0abc17c2cbf63e428b3e76c0455e7e4ee27524b144afa16403b436d6739e")
PARTS = ("setup_before_engine_s", "setup_weights_s", "setup_pools_s",
         "setup_first_calls_s", "setup_ramp_rest_s")

#: a process that started at perf_counter 1000.0: 13.5 s to the
#: constructor, which took 31.0 s (weights 22.0, pools 7.5) and ended at
#: 1044.5; 20 programs before the window, which opened at 1250.0, and one
#: more inside it (15 s, a miss: a cache that had lost it)
T_PROCESS, T0 = 1000.0, 1250.0
AT_CLOSE = {
    "boot_before_ms": 13500.0, "boot_ms": 31000.0,
    "boot_weights_ms": 22000.0, "boot_pools_ms": 7500.0,
    "boot_end_perf_s": 1044.5,
    "compiles": 21, "compile_ms": 165000.0, "compile_trace_ms": 41000.0,
    "compile_lower_ms": 22000.0, "compile_backend_ms": 75500.0,
    "compile_cache_requests": 21, "compile_cache_hits": 15,
    "steps": 5000,
}
IN_WINDOW = {
    "boot_before_ms": 0.0, "boot_ms": 0.0, "boot_weights_ms": 0.0,
    "boot_pools_ms": 0.0, "boot_end_perf_s": 0.0,
    "compiles": 1, "compile_ms": 15000.0, "compile_trace_ms": 1000.0,
    "compile_lower_ms": 2000.0, "compile_backend_ms": 11500.0,
    "compile_cache_requests": 1, "compile_cache_hits": 0,
    "steps": 900,
}
BY_HAND = {
    "setup_before_engine_s": 13.5,
    "setup_weights_s": 22.0,
    "setup_pools_s": 9.0,  # 31.0 - 22.0: the pools' 7.5 and 1.5 of the rest
    "setup_first_calls_s": 150.0,
    "setup_ramp_rest_s": 55.5,  # 1250.0 - 1044.5 - 150.0
    "programs_before_window": 20.0,
    "first_call_trace_s": 40.0,
    "first_call_lower_s": 20.0,
    "first_call_backend_s": 64.0,
    "compile_cache_hit_share": 75.0,  # 15 of 20
}


def ctx_of(at_close: dict, in_window: dict, t0: float = T0) -> dict:
    return {"engine_now": dict(at_close), "engine": dict(in_window),
            "t0": t0, "seconds": 30.0}


def read(name: str, ctx: dict):
    return manifest.layer_reader(name)(ctx)


@pytest.mark.parametrize("name", list(TEN))
def test_a_reader_takes_its_counters_at_the_windows_opening(name):
    value = read(name, ctx_of(AT_CLOSE, IN_WINDOW))
    assert isinstance(value, float)
    assert value == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", list(TEN))
def test_a_reader_gives_a_number_where_nothing_was_counted(name):
    """An engine that loaded no program and asked no cache: 0.0 where the
    denominator is 0, a float everywhere, never None, no raise. Window
    deltas that lack a key read as 0."""
    nothing = dict.fromkeys(AT_CLOSE, 0)
    nothing["boot_end_perf_s"] = T0
    for window in (dict.fromkeys(IN_WINDOW, 0), {}):
        value = read(name, ctx_of(nothing, window))
        assert isinstance(value, float)
        assert value == 0.0


def test_the_five_parts_are_the_set_up_from_the_processs_start():
    ctx = ctx_of(AT_CLOSE, IN_WINDOW)
    parts = [read(name, ctx) for name in PARTS]
    assert sum(parts) == pytest.approx(T0 - T_PROCESS)
    # and the split of the first calls stays inside them
    inside = sum(read(name, ctx) for name in (
        "first_call_trace_s", "first_call_lower_s", "first_call_backend_s"))
    assert inside <= read("setup_first_calls_s", ctx)


def test_an_engine_that_does_not_time_its_boot_is_left_out():
    """The parent's counters: `compiles` and `compile_ms` alone. What
    reads those still reads; every other reader returns None and does
    not raise, and the line leaves the metric out."""
    parent = ctx_of({"compiles": 21, "compile_ms": 165000.0, "steps": 5000},
                    {"compiles": 1, "compile_ms": 15000.0, "steps": 900})
    got = {name: read(name, parent) for name in TEN}
    assert got.pop("setup_first_calls_s") == pytest.approx(150.0)
    assert got.pop("programs_before_window") == 20.0
    assert set(got.values()) == {None}


def test_a_cold_and_a_warm_run_read_0_and_100():
    cold = {**AT_CLOSE, "compile_cache_hits": 0}
    warm = {**AT_CLOSE, "compile_cache_hits": 20}
    assert read("compile_cache_hit_share", ctx_of(cold, IN_WINDOW)) == 0.0
    assert read("compile_cache_hit_share", ctx_of(warm, IN_WINDOW)) == 100.0


# -- the manifest -------------------------------------------------------------


@pytest.fixture(scope="module")
def per_layer():
    return manifest.load()["per_layer"]


def test_the_ten_are_appended_after_the_last_accepted_entry(per_layer):
    names = [m["name"] for m in per_layer]
    at = names.index("hbm_live_with_state_share.cmdaplus")
    assert at == 88  # the 89 accepted entries stand where they stood
    assert names[at + 1:at + 11] == list(TEN)
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", list(TEN))
def test_an_entry_has_just_its_keys_and_a_reader_file(per_layer, name):
    entry = next(m for m in per_layer if m["name"] == name)
    unit, better = TEN[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": LAYER,
                     "moves": "setup_s"}
    assert list(entry) == ["name", "unit", "better", "source", "layer",
                           "moves"]
    assert "workloads" not in entry  # every cell reports it
    assert (manifest.HERE / "layer_metrics" / f"{name}.py").is_file()


def test_every_cell_is_asked_for_all_ten_and_they_move_setup_s():
    man = manifest.load()
    for cell in man["workloads"]:
        wanted = [m["name"] for m in
                  manifest.metrics_of(man, "per_layer", cell["name"])]
        assert [n for n in wanted if n in TEN] == list(TEN)
        assert "setup_s" in [m["name"] for m in manifest.metrics_of(
            man, "end_to_end", cell["name"])]
    moved = [m["name"] for m in man["per_layer"] if m["moves"] == "setup_s"]
    assert moved[:10] == list(TEN)  # the first under it; later ones may follow


def test_the_accepted_entries_are_the_parents(per_layer):
    """Nothing before the ten moved: the first 89 entries are, byte for
    byte as JSON, what they were (their digest, taken on the parent)."""
    import hashlib

    accepted = json.dumps(per_layer[:89], sort_keys=True).encode()
    assert hashlib.sha256(accepted).hexdigest() == ACCEPTED_SHA256
