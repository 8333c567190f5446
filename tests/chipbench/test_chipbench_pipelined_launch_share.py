"""chipbench/layer_metrics/pipelined_launch_share.py: a ratio of window
deltas of the engine's own counters, so a handful of made-up deltas is
all it needs."""
import pytest

from chipbench import manifest


def read(engine: dict):
    return manifest.layer_reader("pipelined_launch_share")({"engine": engine})


@pytest.mark.parametrize("engine, share", [
    # 3.6 admissions and 8.6 decode-carrying dispatches a second over a
    # 30 s window, all but nine launched ahead
    ({"overlap_hits": 249, "decode_dispatches": 150,
      "mixed_dispatches": 108, "prefill_dispatches": 0}, 96.511628),
    # a loop that only keeps a dispatch ahead where no row ends
    ({"overlap_hits": 72, "decode_dispatches": 150,
      "mixed_dispatches": 108}, 27.906977),
    ({"overlap_hits": 0, "decode_dispatches": 4, "mixed_dispatches": 0}, 0.0),
    ({"overlap_hits": 5, "decode_dispatches": 0, "mixed_dispatches": 5},
     100.0),
])
def test_share_of_decode_carrying_dispatches_launched_ahead(engine, share):
    assert read(engine) == pytest.approx(share, abs=1e-6)


@pytest.mark.parametrize("engine", [
    {"overlap_hits": 0, "decode_dispatches": 0, "mixed_dispatches": 0},
    {"overlap_hits": 3, "prefill_dispatches": 7},  # prefill alone
    {"decode_dispatches": 9, "mixed_dispatches": 1},  # no such counter
    {},
])
def test_nothing_to_read_is_none_not_an_error(engine):
    assert read(engine) is None


def test_the_manifest_lists_it_for_every_cell():
    man = manifest.load(None)
    entry = [m for m in man["per_layer"]
             if m["name"] == "pipelined_launch_share"]
    assert len(entry) == 1 and "workloads" not in entry[0]
    assert entry[0]["moves"] == "output_tok_s"
    assert entry[0]["source"] == "program_counter"
    assert man["per_layer"][-1] is entry[0]  # appended, nothing moved
