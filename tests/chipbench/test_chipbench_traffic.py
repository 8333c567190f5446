"""The traffic generator: the closed loop's plan from a seed, its phase
draw and ramp; and the client-side reduction."""
import json

import pytest

from chipbench import client, manifest, traffic

MIXES = sorted(p.stem for p in (manifest.HERE / "traffic").glob("*.json"))


def mix(name):
    with open(manifest.HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def skeleton(p):
    """The work of a plan: every client's sizes, in order."""
    return [[(len(t.new_ids), t.max_tokens) for t in c] for c in p.clients]


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("other", [7, 2**31 + 11])
def test_same_seed_same_plan_other_seed_same_work(name, other):
    m = mix(name)
    a = traffic.plan(m, 3_000_000_019, 32064)
    b = traffic.plan(m, 3_000_000_019, 32064)
    c = traffic.plan(m, other, 32064)
    assert a == b
    assert a != c
    # a seed draws the token ids and nothing else: the same requests of
    # the same sizes, client by client and in the same order
    assert skeleton(a) == skeleton(c)
    prompts = lambda p: {tuple(t.new_ids) for cl in p.clients for t in cl}  # noqa: E731
    assert not prompts(a) & prompts(c)
    ids = [t for cl in a.clients for turn in cl for t in turn.new_ids]
    assert min(ids) >= traffic.FIRST_ID and max(ids) < 32064


@pytest.mark.parametrize("name", MIXES)
def test_sizes_keep_to_the_mix(name):
    m = mix(name)
    p = traffic.plan(m, 1, 152064)
    assert len(p.clients) == m["clients"]
    assert all(len(c) == m["requests_per_client"] for c in p.clients)
    later = [t for c in p.clients for t in c[1:]]
    lens = [len(t.new_ids) for t in later]
    assert min(lens) >= m["prompt_tokens"]["min"]
    assert max(lens) <= m["prompt_tokens"]["max"]
    assert len(set(lens)) > 50
    outs = [t.max_tokens for t in later]
    assert min(outs) >= m["output_tokens"]["min"]
    assert max(outs) <= m["output_tokens"]["max"]
    # the burst that meets the idle engine has one prompt length
    assert {len(c[0].new_ids) for c in p.clients} == {
        m["first_prompt_tokens"]["value"]}
    # the longest sequence fits the served context
    assert m["prompt_tokens"]["max"] + m["output_tokens"]["max"] < 8192


def test_closed_loop_phase_draw_staggers_first_answers():
    m = mix("longgen")
    p = traffic.plan(m, 5, 152064)
    first = [c[0].max_tokens for c in p.clients]
    assert min(first) < m["output_tokens"]["min"]  # cut to a fraction
    assert min(first) >= 4
    assert len(set(first)) > len(first) // 2
    # the phase belongs to the client, not to the seed
    q = traffic.plan(m, 6, 152064)
    assert first == [c[0].max_tokens for c in q.clients]


def test_the_ramp_is_a_point_of_the_plan_and_its_lead_covers_a_decode():
    m = mix("longgen")
    p = traffic.plan(m, 1, 152064)
    assert p.ramp_tokens == m["ramp_tokens"] > 0
    assert p.ramp_lead_s == m["ramp_lead_s"]
    # what was sent before the window was announced has decoded its
    # longest answer (28 tokens a second) when the window ends, so only
    # requests that waited long for a slot are left over there
    man = manifest.load()
    assert (p.ramp_lead_s + man["run_seconds"]
            > m["output_tokens"]["max"] / 28.0 + 3.0)


LOGNORMAL = {"dist": "lognormal", "median": 256, "sigma": 0.6,
             "min": 64, "max": 512}


def test_lognormal_is_whole_clipped_and_centred():
    import numpy as np

    got = traffic.draw(LOGNORMAL, np.random.default_rng(5), 20_000)
    assert all(isinstance(v, int) for v in got)
    assert min(got) == 64 and max(got) == 512
    assert 246 <= sorted(got)[len(got) // 2] <= 266
    # ln(2) / 0.6 = 1.155 standard deviations above the median: 12.4 %
    assert 0.11 < sum(v == 512 for v in got) / len(got) < 0.14
    assert 0.005 < sum(v == 64 for v in got) / len(got) < 0.02
    narrow = traffic.draw({**LOGNORMAL, "sigma": 0.01},
                          np.random.default_rng(5), 100)
    assert set(narrow) <= set(range(245, 268))


@pytest.mark.parametrize("other", [7, 2**31 + 11])
def test_a_lognormal_plan_follows_the_shape_seed_and_never_the_seed(other):
    m = mix("chat-closed")
    assert m["prompt_tokens"]["dist"] == m["output_tokens"]["dist"] \
        == "lognormal"
    a = traffic.plan(m, 3_000_000_019, 32064)
    assert skeleton(a) == skeleton(traffic.plan(m, other, 32064))
    moved = traffic.plan({**m, "shape_seed": m["shape_seed"] + 1},
                         3_000_000_019, 32064)
    assert skeleton(a) != skeleton(moved)
    assert [len(c) for c in moved.clients] == [len(c) for c in a.clients]


def test_chat_closed_is_the_issues_mix():
    m = mix("chat-closed")
    assert (m["loop"], m["clients"]) == ("closed", 16)
    assert m["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert m["phase_first_request"] is True
    for key, median, sigma, lo, hi in (("prompt_tokens", 256, 0.6, 64, 512),
                                       ("output_tokens", 128, 0.8, 16, 512)):
        d = m[key]
        assert (d["median"], d["sigma"], d["min"], d["max"]) == \
            (median, sigma, lo, hi)
    p = traffic.plan(m, 1, 32064)
    assert {len(c[0].new_ids) for c in p.clients} == {256}
    # phi3's sliding window (2047) never binds: no sequence passes 1,024
    assert max(len(t.new_ids) + t.max_tokens
               for c in p.clients for t in c) <= 1024
    prompts = {tuple(t.new_ids) for c in p.clients for t in c}
    assert len(prompts) == 16 * m["requests_per_client"]  # nothing shared


def test_unknown_loop_or_distribution_is_an_error():
    m = mix("longgen")
    with pytest.raises(ValueError):
        traffic.plan({**m, "loop": "open"}, 1, 1000)
    with pytest.raises(ValueError):
        traffic.plan({**m, "prompt_tokens": {"dist": "zipf"}}, 1, 1000)


def result(due, sent, chunks, want, measured=True, status=200, done=None):
    r = client.Result(due=due, sent=sent, measured=measured, status=status,
                      want_tokens=want, prompt_tokens=100)
    r.chunks = chunks
    r.completion_tokens = sum(n for _t, n in chunks)
    r.done = done if done is not None else (chunks[-1][0] if chunks else sent)
    return r


def test_gaps_are_per_token_between_coalesced_deliveries():
    # 8 one-token chunks back to back, then 8 more 160 ms later
    burst = [(1.0 + i * 1e-4, 1) for i in range(8)]
    burst2 = [(1.16 + i * 1e-4, 1) for i in range(8)]
    r = result(0.9, 0.9, burst + burst2, 16)
    assert [n for _t, n in r.deliveries()] == [8, 8]
    assert r.gaps_ms() == [(pytest.approx(1.16), pytest.approx(160.0 / 8))]


def test_reduce_reads_the_window_and_nothing_else():
    t0, seconds = 100.0, 10.0
    ok = result(101.0, 101.05, [(101.3, 1), (101.32, 1)], 2)
    failed = result(103.0, 103.0, [], 4, status=500)
    # began in the ramp, completed in the window: a request of the window,
    # but its gap and tokens of before t0 are not
    over = result(95.0, 95.0, [(99.0, 1), (99.5, 1), (100.5, 1), (101.0, 1)],
                  4)
    before = result(90.0, 90.0, [(90.5, 1), (91.5, 1)], 2, measured=False)
    red = client.reduce([ok, failed, over, before], t0, seconds)
    assert red["attempted"] == 3 and red["failed"] == 1
    assert red["failures"][0]["status"] == 500
    assert red["ttft_ms"] == [pytest.approx(300.0)]  # sent in the window
    assert red["late_ms"] == [pytest.approx(50.0), 0.0]
    assert sorted(red["gaps_ms"]) == [pytest.approx(20.0),
                                      pytest.approx(500.0),
                                      pytest.approx(1000.0)]
    assert red["output_tok_s"] == pytest.approx(4 / 10.0)
    # a stream the window's end cut is no request of the window, but the
    # gaps and tokens it delivered inside the window count
    cut = result(105.0, 105.0, [(106.0, 1), (106.5, 1), (110.5, 1)], 9,
                 measured=False)
    cut.cut = True
    red = client.reduce([ok, cut], t0, seconds)
    assert red["attempted"] == 1 and red["cut"] == 1
    assert sorted(red["gaps_ms"]) == [pytest.approx(20.0),
                                      pytest.approx(500.0)]
    assert red["output_tok_s"] == pytest.approx(4 / 10.0)
    short = result(101.0, 101.0, [(101.1, 1)], 2)  # fewer tokens than asked
    assert client.reduce([short], t0, seconds)["failed"] == 1


@pytest.mark.parametrize("case,cut", [
    ("left_at_the_end", True),
    ("deadline_while_queued", True),
    ("deadline_while_streaming", True),
    ("error_finish_before_the_end", False),
    ("refused_just_before_the_end", False),
    ("short_stream_at_the_end", False),
])
def test_only_the_windows_end_cuts_a_request(case, cut):
    """A request that is not ok() is a failure whenever it ends, unless
    the window's end ended it: the client left the stream there, or the
    server's deadline did (504 queued, `error` finish streaming)."""
    t_end = 130.0
    r = result(120.0, 120.0, [(121.0, 1)], 8, done=130.02)
    if case == "left_at_the_end":
        r.left = True
    elif case == "deadline_while_queued":
        r.status, r.chunks = 504, []
    elif case == "deadline_while_streaming":
        r.finish = "error"
    elif case == "error_finish_before_the_end":
        r.finish, r.done = "error", 129.9
    elif case == "refused_just_before_the_end":
        r.status, r.chunks, r.done = 500, [], 129.95
    elif case == "short_stream_at_the_end":
        r.finish = "length"
    assert not r.ok()
    assert r.ended_by_window(t_end) is cut
    assert r.ended_by_window(None) is False
