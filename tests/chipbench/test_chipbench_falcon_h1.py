"""`falcon-h1-34b-1chip` and `falconh1-longdoc` through the seam PR 26
built: the configuration file against the published numbers, the served
widths and multipliers, the cost module on hand-computed bytes and
operations, the new per-layer readers (four that call an existing reader,
one of their own), the control's five lowerings, the state's precision,
the plan's program family, and the cell's CPU rehearsal."""
import collections
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, manifest, reference, run, traffic
from dynamo_tpu.models import falcon_h1 as fh
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.registry import get_model
from test_chipbench_deepseek_v2_lite import PEAKS
from test_chipbench_nemotron_h import (  # noqa: F401 — `run_dir` a fixture
    BODY, MIXED, run_dir)

#: Falcon-H1-34B-Instruct's config.json as published (the catalog's row,
#: every key)
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120,
}
NEW = ("ssm_ms_per_step.falconh1", "ssm_scan_hbm_share.falconh1",
       "ssm_chunk_flops_share.falconh1", "state_slots_live_share.falconh1",
       "hbm_live_with_state_share")
CELL = "falconh1-longdoc"


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def conf(man):
    return manifest.config_of(man, manifest.cell(man, CELL))


@pytest.fixture(scope="module")
def cost(conf):
    return manifest.module_of(conf, "costs_module", costs)


def test_the_file_holds_every_published_number_but_the_two_it_lists(
        man, conf):
    entry = next(c for c in man["configs"]
                 if c["name"] == "falcon-h1-34b-1chip")
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
        "config.json")
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    differ = sorted(k for k, v in PUBLISHED.items()
                    if k not in conf or conf[k] != v)
    assert differ == sorted(conf["reduced"])
    assert conf["num_hidden_layers"] == 6
    assert conf["max_position_embeddings"] == 8192
    assert (conf["num_hidden_layers_published"],
            conf["max_position_embeddings_published"]) == (72, 262144)
    assert conf["torch_dtype"] == "bfloat16"
    # all nine multipliers, unchanged
    for key in fh.MULTIPLIERS:
        assert conf[key] == PUBLISHED[key], key
    assert "pipeline over depth" in conf["deployment"]
    assert "6 of the 72 layers" in conf["deployment"]
    assert "What the cut distorts" in conf["deployment"]
    for key in (*conf["reduced"], "weights", "ssm_state_dtype", "num_pages",
                "state_slots", "max_seqs"):
        assert key in conf["assumed"], key
    assert "1 / (c sqrt(fan_in))" in conf["assumed"]["weights"]
    tol = conf["reference_tolerance"]
    assert set(tol) >= {"min_argmax_agreement", "max_logprob_drift",
                        "max_mean_logprob_drift", "max_ssm_state_distance",
                        "why"}
    # one configuration and one cell (not pinned to the lists' ends: a later
    # PR appends its own)
    cells = [w for w in man["workloads"]
             if w["config"] == "falcon-h1-34b-1chip"]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "falcon-h1-34b-1chip", "longdoc", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_every_published_width_and_multiplier_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", reference)
    assert ref.__file__ == str(manifest.HERE / "references" / "falcon_h1.py")
    cfg = get_model(conf["preset"], dtype="bfloat16",
                    attention_impl="pallas").config
    widths = run.served_widths(cfg, ref)
    assert set(widths) >= {
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
        "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
        "mamba_chunk_size", *fh.MULTIPLIERS}
    assert all(k in conf and widths[k] == conf[k] for k in widths)
    assert cfg.attention_impl == "pallas" and cfg.dtype == jnp.bfloat16
    assert cfg.num_layers == 6 and cfg.attn_cfg.use_rope
    # the serve flags: 32 + 4 slots, pages of 64 tokens
    flags = dict(zip(conf["serve_flags"][::2], conf["serve_flags"][1::2]))
    assert flags["--max-seqs"] == "32" and flags["--max-context"] == "8192"
    # no flag ISSUE 34 did not name: the engine's own step budget (four
    # chunks) and chunk (512) serve the cell
    assert set(flags) == {"--dtype", "--num-pages", "--max-seqs",
                          "--max-context"}
    # the rehearsal preset under the rehearsal's own keys
    tiny = get_model(conf["rehearsal"]["preset"]).config
    hf = conf["rehearsal"]["hf"]
    small = run.served_widths(tiny, ref)
    assert all(k in hf and small[k] == hf[k] for k in small)
    # every key the reference reads is in both
    assert set(ref.HF_KEYS) <= set(hf) and set(ref.HF_KEYS) <= set(conf)


def test_costs_on_hand_computed_bytes_and_operations(conf, cost):
    assert cost.__file__ == str(manifest.HERE / "costs_falcon_h1.py")
    w = {"itemsize": 2}
    # a row's state: 6 layers x (32 x 128 x 256 x 4 B + 3 x 5120 x 2 B)
    one = 32 * 128 * 256 * 4 + 3 * 5120 * 2
    assert one == 4_225_024
    assert cost.ssm_state_bytes_per_row(conf) == 6 * one == 25_350_144
    assert cost.ssm_state_bytes(conf, w, 0, 32) == 2 * 32 * 6 * one
    assert cost.ssm_state_bytes(conf, w, 0, 32) == pytest.approx(1.62e9,
                                                                 rel=0.005)
    # a token's K and V: 6 layers x 4 heads x 128 x 2 x 2 B
    assert cost.kv_bytes_per_token(conf) == 12_288
    assert cost.kv_read_bytes(conf, w, 166_000, 32) == 166_000 * 12_288
    # a row's pages pass its state at 2,063 tokens
    assert 6 * one / 12_288 == pytest.approx(2063, abs=1)
    # a layer's parameters by part (the issue's arithmetic)
    part = cost.layer_weight_params(conf)
    assert part["attention"] == 5120 * (2560 + 2 * 512) + 2560 * 5120
    assert part["attention"] == 31_457_280
    assert part["mamba"] + part["mamba_f32"] == 68_351_072
    assert part["mlp"] == 3 * 5120 * 21504 == 330_301_440
    # every streamed weight, against the program's own tree
    cfg = get_model(conf["preset"], dtype="bfloat16").config
    tree = jax.eval_shape(lambda: fh.init_params(jax.random.key(0), cfg))
    nbytes = lambda t: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(t))
    assert cost.weight_bytes(conf, with_embed=True) == nbytes(tree)
    assert nbytes(tree) == pytest.approx(10.51e9, rel=0.002)
    streamed = nbytes(tree) - nbytes(tree["embed"])
    assert cost.weight_bytes(conf) == streamed
    assert streamed == pytest.approx(7.84e9, rel=0.002)
    assert cost.step_read_bytes(conf, w, 0, 32) == streamed + 2 * 32 * 6 * one
    assert cost.step_read_bytes(conf, w, 1000, 32) - cost.step_read_bytes(
        conf, w, 0, 32) == 1000 * 12_288
    # the issue's floor at 32 rows and ~166k live tokens: 11.5 GB, 14.0 ms
    floor = cost.step_read_bytes(conf, w, 32 * 5200, 32)
    assert floor == pytest.approx(11.5e9, rel=0.01)
    assert floor / 819e9 == pytest.approx(14.0e-3, rel=0.01)
    # a prompt chunk's conv and scan, by hand for one token of a 512 chunk
    per_token = (2 * 2 * 256 * 128 / 2 + 2 * 32 * 128 * 128 / 2
                 + 4 * 32 * 128 * 256 + 2 * 4 * 5120)
    assert cost.ssm_chunk_flops(conf, 512) == 6 * 512 * per_token
    assert cost.ssm_chunk_flops(conf, 512) == pytest.approx(1.48e10, rel=0.02)
    # and at the toy size against the program's trees and pools
    tiny = fh.FalconH1Config.tiny()
    hf = conf["rehearsal"]["hf"]
    small = fh.init_params(jax.random.key(0), tiny)
    assert cost.step_read_bytes(hf, {"itemsize": 4}, 0, 10) == \
        nbytes(small) - small["embed"].nbytes + 2 * 10 * (
            nh.state_bytes_per_slot(tiny))
    cache = nh.init_cache(tiny, 8, 4, 3)
    assert cost.ssm_state_bytes_per_row(hf, 4) * 8 == \
        cache.conv.nbytes + cache.ssm.nbytes
    assert cost.kv_read_bytes(hf, {"itemsize": 4}, 32, 1) == \
        cache.k.nbytes + cache.v.nbytes


# -- the new readers on a made-up trace ----------------------------------------

#: one fused dispatch of two steps (36 ms), one mixed step (10 ms): both
#: mixers under `attn` in every layer
FALCON_OPS = [
    ("%while.1", 0, 36000, "jit(multi_fn)/while:"),
    ("%fusion.2", 0, 1500, BODY + "attn/ssm/in_proj/dot_general:"),
    ("%fusion.3", 1500, 500, BODY + "attn/ssm/conv/add:"),
    ("%kernel.4", 2000, 500, BODY + "attn/ssm/conv/state_write_rows:"),
    ("%kernel.5", 2500, 5000, BODY + "attn/ssm/scan/ssm_decode_step:"),
    ("%fusion.6", 7500, 300, BODY + "attn/ssm/gate_norm/mul:"),
    ("%fusion.7", 7800, 700, BODY + "attn/ssm/out/dot_general:"),
    ("%fusion.8", 8500, 500, BODY + "attn/qkv/dot_general:"),
    ("%kernel.9", 9000, 7000, BODY + "attn/paged/paged_decode_attention:"),
    ("%fusion.10", 16000, 500, BODY + "attn/out/dot_general:"),
    ("%fusion.11", 16500, 11500, BODY + "mlp/dot_general:"),
    ("%fusion.12", 28000, 8000, "jit(multi_fn)/while/body/lm_head/dot:"),
    ("%fusion.20", 40000, 1000, MIXED + "attn/ssm/in_proj/dot_general:"),
    ("%fusion.21", 41000, 1000, MIXED + "attn/ssm/conv/add:"),
    ("%fusion.22", 42000, 4000, MIXED + "attn/ssm/scan/dot_general:"),
    ("%fusion.23", 46000, 4000, MIXED + "mlp/dot_general:"),
]


def reader_ctx(conf) -> dict:
    fused = {"kind": "decode_multi", "n_decode": 32, "tokens": 64}
    return {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1, "active_pages": 2600},
                   {"kind": "mixed", "ts": 100.2, "n_decode": 31,
                    "n_prefill": 1, "prefill_tokens": 512, "tokens": 32,
                    "active_pages": 2600}],
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {},
        "engine_now": {"state_slots": 36, "state_slots_live": 33,
                       "kv_total_pages": 3299, "kv_pages_watermark": 2700},
        "memory": {"weights_bytes": 10_510_000_000,
                   "kv_pool_bytes": 3300 * 786_432,
                   "state_pool_bytes": 74 * 25_350_144},
        "costs": manifest.module_of(conf, "costs_module", costs),
    }


def test_new_readers_on_the_cells_trace(conf, cost, run_dir):  # noqa: F811
    run_dir(FALCON_OPS)
    ctx = reader_ctx(conf)
    read = manifest.layer_reader
    # two fused steps: all of attn/ssm is 8.5 ms, conv + scan 6 ms
    assert read("ssm_ms_per_step.falconh1")(ctx) == pytest.approx(4.25)
    state = 2 * 32 * 6 * 4_225_024
    assert read("ssm_scan_hbm_share.falconh1")(ctx) == pytest.approx(
        100.0 * state / 3e-3 / 819e9, rel=1e-6)
    flops = cost.ssm_chunk_flops(conf, 512)
    assert read("ssm_chunk_flops_share.falconh1")(ctx) == pytest.approx(
        100.0 * flops / 5e-3 / 197e12, rel=1e-6)
    assert read("state_slots_live_share.falconh1")(ctx) == pytest.approx(
        100.0 * 33 / 36)
    live = 10.51e9 + 2700 * 786_432 + 2 * 33 * 25_350_144
    assert read("hbm_live_with_state_share")(ctx) == pytest.approx(
        100.0 * live / 16e9)
    # what `hbm_live_share` under-reads by: the slot entries held
    assert read("hbm_live_with_state_share")(ctx) - read("hbm_live_share")(
        ctx) == pytest.approx(100.0 * 2 * 33 * 25_350_144 / 16e9)
    for name in NEW[1:]:
        assert 0 < read(name)(ctx) <= 100, name
    # each `.falconh1` reader IS the reader it is named after
    for name in NEW[:4]:
        base = name.rsplit(".", 1)[0]
        assert read(name)(ctx) == read(base)(ctx)
        src = (manifest.HERE / "layer_metrics" / f"{name}.py").read_text()
        assert f'manifest.layer_reader("{base}")' in src
        assert "def read" not in src  # no copied body
    # the readers the benchmark had read the same trace with no edit: both
    # mixers inside `attn`, the page walk from the flight record's tokens
    assert read("decode_attn_ms_per_step")(ctx) == pytest.approx(8.25)
    assert read("decode_mlp_ms_per_step")(ctx) == pytest.approx(5.75)
    assert read("decode_head_ms_per_step")(ctx) == pytest.approx(4.0)
    tokens = 2600 * 64 - 32 * 32
    assert read("paged_attn_hbm_share")(ctx) == pytest.approx(
        100.0 * tokens * 12_288 / 3.5e-3 / 819e9, rel=1e-4)


def test_new_readers_give_none_where_there_is_nothing_to_read(
        conf, run_dir):  # noqa: F811
    """The parent commit's programs, or another configuration's: no such
    scope in the trace, no state pool, nothing to read, no error."""
    run_dir([(n, s, d, p.replace("attn/ssm/scan", "attn/paged").replace(
        "attn/ssm/conv", "attn/kv_update").replace("attn/ssm/", "attn/"))
        for n, s, d, p in FALCON_OPS])
    ctx = reader_ctx(conf)
    ctx = {**ctx, "engine_now": {"kv_total_pages": 100,
                                 "kv_pages_watermark": 50},
           "memory": {"weights_bytes": 1, "kv_pool_bytes": 2}}
    for name in NEW:
        assert manifest.layer_reader(name)(ctx) is None, name
    # a state pool in the counters but none in the memory report, or no
    # peaks (a CPU rehearsal)
    ctx = reader_ctx(conf)
    assert manifest.layer_reader("hbm_live_with_state_share")(
        {**ctx, "memory": {"weights_bytes": 1, "kv_pool_bytes": 2}}) is None
    assert manifest.layer_reader("hbm_live_with_state_share")(
        {**ctx, "peaks": None}) is None


def test_the_new_metrics_name_the_cell_and_the_cell_reports_the_old_ones(man):
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "output_tok_s"
        assert set(per_layer[name]) == {"name", "unit", "better", "source",
                                        "layer", "moves", "workloads"}
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW[0])
    assert names[at : at + 5] == list(NEW)  # appended together, in order
    layers = {m["layer"] for m in man["per_layer"][:at]}
    assert {per_layer[n]["layer"] for n in NEW} <= layers
    wanted = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW) <= wanted
    assert {"paged_attn_hbm_share", "decode_hbm_share", "hbm_live_share",
            "decode_attn_ms_per_step", "decode_mlp_ms_per_step",
            "decode_head_ms_per_step", "pipelined_launch_share",
            "mixed_step_device_ms"} <= wanted
    # the `ssm_*` metrics PR 31 added list their cell and stay its own
    assert wanted.isdisjoint({
        "ssm_ms_per_step", "ssm_scan_hbm_share", "ssm_chunk_flops_share",
        "state_slots_live_share", "moe_experts_hbm_share",
        "itl_p95_ms.longgen"})
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", CELL)} == {"output_tok_s", "setup_s"}
    for cell in ("qwen2-longgen", "phi3-chat-closed", "dsv2lite-docgen",
                 "nano3-chat-churn"):
        assert set(NEW).isdisjoint(m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell))


# -- the traffic's plan ----------------------------------------------------------


def _bucket(n: int) -> int:
    t = 32
    while t < n:
        t *= 2
    return min(t, 512)


def test_the_traffic_is_the_issues(man):
    mix = manifest.traffic_of(manifest.cell(man, CELL))
    assert (mix["loop"], mix["clients"], mix["requests_per_client"]) == (
        "closed", 40, 8)
    assert mix["prompt_tokens"] == {
        "dist": "uniform_int", "min": 3073, "max": 6144,
        "why": mix["prompt_tokens"]["why"]}
    assert mix["output_tokens"] == {
        "dist": "uniform_int", "min": 768, "max": 1536,
        "why": mix["output_tokens"]["why"]}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert mix["phase_first_request"] is True
    assert mix["ramp_tokens"] >= 60_000
    for key in ("why", "requests_why", "ramp_why"):
        assert len(mix[key]) > 100
    assert "rehearsal" in mix
    plan = traffic.plan(mix, 3_000_000_019, 261120)
    assert len(plan.clients) == 40
    # fresh ids every prompt: no two prompts share their first page
    heads = [tuple(t.new_ids[:64]) for c in plan.clients for t in c]
    assert len(set(heads)) == len(heads)
    firsts = [len(c[0].new_ids) for c in plan.clients]
    assert set(firsts) == {mix["first_prompt_tokens"]["value"]} == {32}
    # the burst that meets an idle engine fits one prefill step of the
    # engine's default budget (four chunks of 512)
    assert 32 * firsts[0] <= 4 * 512


def test_the_ramp_meets_every_member_of_the_step_family(man, conf):
    """The plan is the same in every run (`shape_seed`), and eight
    requests wait whenever a row ends, so which prompts prefill side by
    side is a property of the file, not of the clock. A coarse simulation
    of the closed loop (32 slots, the scheduler's own piece rule under the
    engine's default step budget of four chunks: pieces in the rows'
    order, a mid-prompt piece ends on a page; the engine's grouping: the
    pieces of the largest T bucket fused with the decode rows, the others
    a prefill dispatch a bucket beside the step) meets every (kind, piece
    rows, T bucket, first chunk, sampled) member the plan EVER meets well
    before `ramp_tokens` are delivered, whether the first request arrives
    alone or with the others; the clients outlast ramp, lead and window,
    and the pages the plan ever holds fit the pool."""
    mix = manifest.traffic_of(manifest.cell(man, CELL))
    flags = dict(zip(conf["serve_flags"][::2], conf["serve_flags"][1::2]))
    assert "--prefill-budget" not in flags and "--prefill-chunk" not in flags
    pool = int(flags["--num-pages"])
    plan = traffic.plan(mix, 1, 1000)

    def walk(first_step_rows):
        nxt, queue = [0] * 40, collections.deque(range(40))
        running, delivered, first_seen, most_pages = [], 0, {}, 0
        step = 0
        while queue or running:
            step += 1
            cap = first_step_rows if step == 1 else 32
            while queue and len(running) < cap:
                c = queue.popleft()
                turn = plan.clients[c][nxt[c]]
                running.append({"c": c, "p": len(turn.new_ids), "done": 0,
                                "out": turn.max_tokens,
                                "want": turn.max_tokens})
            pieces, budget = [], 4 * 512
            for r in running:
                if r["done"] >= r["p"] or budget <= 0:
                    continue
                left = r["p"] - r["done"]
                take = min(left, 512, budget)
                if take < left:
                    take = take // 64 * 64
                if take > 0:
                    pieces.append((r, take))
                    budget -= take
            n_dec = sum(1 for r in running if r["done"] >= r["p"])
            if pieces:
                groups: dict = {}
                for r, t in pieces:
                    groups.setdefault(_bucket(t), []).append((r, t))
                members = []
                if n_dec:
                    members.append(("mixed", groups.pop(max(groups))))
                members += [("prefill", g) for g in groups.values()]
                for kind, g in members:
                    n = 1
                    while n < len(g):
                        n *= 2
                    member = (kind, n, _bucket(max(t for _r, t in g)),
                              all(r["done"] == 0 for r, _t in g),
                              any(r["done"] + t >= r["p"] for r, t in g))
                    if n_dec > 16:  # the steady state's 32-row programs
                        first_seen.setdefault(member, delivered)
            steps = 1 if pieces else 8
            fed = {id(r) for r, _t in pieces}
            for r, t in pieces:
                r["done"] += t
            for r in list(running):
                if r["done"] < r["p"]:
                    continue
                k = 1 if id(r) in fed else min(steps, r["out"])
                r["out"] -= k
                delivered += k
                if r["out"] <= 0:
                    running.remove(r)
                    nxt[r["c"]] += 1
                    if nxt[r["c"]] < len(plan.clients[r["c"]]):
                        queue.append(r["c"])
            if delivered > 50_000:
                most_pages = max(most_pages, sum(
                    -(-(r["p"] + r["want"] - r["out"]) // 64)
                    for r in running))
        return first_seen, delivered, most_pages

    for first_step_rows in (1, 32):
        first_seen, delivered, most_pages = walk(first_step_rows)
        # one, two and four pieces beside the decode rows, and the tails
        # of the smaller buckets as prefill dispatches of one row
        assert {m[1] for m in first_seen if m[0] == "mixed"} == {1, 2, 4}
        assert {m[1] for m in first_seen if m[0] == "prefill"} == {1}
        assert 15 <= len(first_seen) <= 20, first_seen
        # the last of them long before the window is announced
        assert max(first_seen.values()) < 0.6 * mix["ramp_tokens"]
        # ramp + lead and window at 1,600 tokens a second
        assert delivered > mix["ramp_tokens"] + 1600 * (
            mix["ramp_lead_s"] + 30) + 90_000
        # the pool holds what the plan ever holds, with room
        assert most_pages + 100 < pool


# -- the control -------------------------------------------------------------------


def test_the_control_lowers_each_of_its_five_ways(conf):
    ref = manifest.module_of(conf, "reference_module", reference)
    assert list(ref.CONTROLS) == [
        "bf16_state", "int8_weights", "key_multiplier_1",
        "ssm_multipliers_bc_swapped", "no_attention"]
    tiny = fh.FalconH1Config.tiny()
    hf = conf["rehearsal"]["hf"]
    params = fh.init_params(jax.random.key(0), tiny)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    low = ref.to_int8(lp)
    for name in ref.MATRICES:
        w, q = np.asarray(lp[name]), np.asarray(low[name])
        scale = np.abs(w).max(axis=-2, keepdims=True) / 127.0
        np.testing.assert_allclose(q / scale, np.round(q / scale), atol=1e-3)
        assert 0 < np.abs(q - w).max() <= scale.max() / 2 + 1e-6
    assert np.array_equal(np.asarray(low["A_log"]), np.asarray(lp["A_log"]))
    swapped = ref.CONTROLS["ssm_multipliers_bc_swapped"]["hf"](hf)
    have = hf["ssm_multipliers"]
    assert swapped["ssm_multipliers"] == [
        have[0], have[1], have[3], have[2], have[4]]
    ids = np.random.default_rng(0).integers(10, 256, 40)
    base = ref.log_probs(params, hf, ids, [39])[0]
    moved = {}
    for name, how in ref.CONTROLS.items():
        how = dict(how)
        if "state_dtype" in how:
            how["state_dtype"] = jnp.dtype(how["state_dtype"])
        over = how.pop("hf", lambda hf: {})(hf)
        moved[name] = ref.log_probs(params, {**hf, **over}, ids, [39],
                                    **how)[0]
    for name, lp_ in moved.items():
        assert np.abs(lp_ - base).max() > 1e-4, name
    # a multiplier and a whole branch are not roundings
    for name in ("key_multiplier_1", "ssm_multipliers_bc_swapped",
                 "no_attention"):
        assert np.abs(moved[name] - base).max() > 0.02, name


@pytest.mark.parametrize("case", ["program", "program_kernel", "bf16_control"])
def test_state_distance_reads_the_precision_the_state_is_carried_in(
        conf, case, monkeypatch):
    """`compare` holds the state to float32 (`max_ssm_state_distance`)
    the way nano3's does: the program's pool and decode routine, jnp and
    the kernel blocked over heads, read a rounding of float32; the
    control's bfloat16 recurrence reads a rounding of bfloat16 and fails
    by that limit alone, through the one key of the harness's verdict
    that carries it."""
    import functools

    from dynamo_tpu.ops import ssm_state

    ref = manifest.module_of(conf, "reference_module", reference)
    tol = conf["reference_tolerance"]
    hf = {**conf["rehearsal"]["hf"], "reference_tolerance": tol}
    params = fh.init_params(jax.random.key(0), fh.FalconH1Config.tiny())
    how = ({"state_dtype": jnp.dtype("bfloat16")}
           if case == "bf16_control" else {})
    streams = ref.control_streams(params, hf, 7, how, prompt_len=12,
                                  out_len=24, streams=1)
    # only a control that lowers the state brings one (one a layer): the
    # others are judged by their log-probs, the state is the program's own
    assert ("ssm_state" in streams[0]) == (case == "bf16_control")
    if case == "bf16_control":
        assert len(streams[0]["ssm_state"]) == 3
    if case == "program_kernel":
        # two heads a block: the grid runs over blocks of heads
        monkeypatch.setattr(ssm_state, "STATE_BLOCK_BYTES", 2 * 16 * 16 * 4)
        monkeypatch.setattr(ssm_state, "ssm_decode_step", functools.partial(
            ssm_state.ssm_decode_step, use_kernel=True, interpret=True))
    res = ref.compare(params, hf, streams)
    if case.startswith("program"):
        assert res["ssm_state_distance"] < 1e-6
        assert res["mean_logprob_drift"] < 1e-5
        return
    assert res["ssm_state_distance"] > 10 * tol["max_ssm_state_distance"]
    assert res["mean_logprob_drift"] == float("inf")
    # nothing else tells it: the tokens' own drift is far inside its limit
    assert (res["mean_logprob_drift_of_tokens"]
            < tol["max_mean_logprob_drift"] / 2)
    # without the served preset's name there is no pool to ask
    assert "ssm_state_distance" not in ref.compare(
        params, {k: v for k, v in hf.items() if k != "preset"}, streams)


def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset falcon-h1-tiny, float32, `--attention-impl pallas`: chunked
    prefill from a state slot over paged history, the fused decode
    dispatch through both caches of every layer, mixed steps,
    launch-ahead, through run in=http, and the reference agrees. Never a
    result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(manifest.ROOT)}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "5", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert last["failed"] == 0
    assert set(last["metrics"]) == {"output_tok_s", "setup_s"}
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert notes["serve_up"]["model"] == "falcon-h1-tiny"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    memory = notes["serve_up"]["memory"]
    # 8 + 1 slots, two generations and the null entries, three layers
    assert memory["state_pool_bytes"] == 20 * 3 * (
        4 * 16 * 16 * 4 + 3 * 128 * 4)
    # k and v, three layers, 1024 pages of 4 tokens, 2 KV heads of 16
    # cached as 128 lanes under the kernels
    assert memory["kv_pool_bytes"] == 2 * 3 * 1024 * 4 * 2 * 128 * 4
    assert notes["correct"]["widths_as_published"] is True
    assert notes["reference"]["passed"] is True
    assert notes["reference"]["tokens"] == 128
    assert notes["reference"]["max_logprob_drift"] < 1e-3
    assert notes["reference"]["ssm_state_distance"] < 1e-6
    kinds = {p["key"].split(",")[0].strip("('") for p in
             notes["programs"]["seen"]}
    assert {"mixed", "decode_multi"} <= kinds
    assert notes["window"]["preemptions"] == 0
