"""`mimo-v2.5-1chip` and `mimo25-longctx` through the seam PR 26 built: the
configuration file against the published numbers, the served widths, the
cost module on hand-computed bytes and FLOPs, the ten new entries and
their reader files on a made-up trace, the plan's walk under `longctx` at
this cell's slots, the control's lowerings, the harness's verdict on the
tiny program in this process (`check_reference` through the reference
module's `compare`: the streams, the attention at depth through a wrapped
ring under the interpreted kernels, a planted fault) and, behind `-m slow`,
a rehearsal of the cell through `chipbench.run` and HTTP (on fewer requests
than `longctx`'s `rehearsal` block asks, and still 68 s here and 140 s
beside five other workers, 20 of them imports: no subprocess that serves
interpreted step programs fits 30 s)."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import cmdaplusscopes, costs, manifest, traffic
from dynamo_tpu.models.registry import get_model
from test_chipbench_command_a_plus import trace_dir  # noqa: F401 (fixture)
from test_chipbench_deepseek_v2_lite import PEAKS
from test_chipbench_minicpm_sala import _walk

F, S = "full_attention", "sliding_attention"
#: the catalog row's `config` (MiMo-V2.5's config.json, the language
#: model), every key
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0],
    "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576, "model_type": "mimo_v2",
    "moe_intermediate_size": 2048, "moe_layer_freq": [0] + [1] * 47,
    "n_group": 1, "n_routed_experts": 256, "n_shared_experts": None,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576,
}
NEW = ("window_attn_ms_per_step.mimo25", "window_attn_hbm_share.mimo25",
       "full_attn_hbm_share.mimo25", "window_chunk_flops_share.mimo25",
       "full_chunk_flops_share.mimo25", "moe_experts_hbm_share.mimo25",
       "moe_route_ms_per_step.mimo25", "window_tokens_attended_share.mimo25",
       "state_slots_live_share.mimo25", "hbm_live_with_state_share.mimo25")
CELL, CONFIG = "mimo25-longctx", "mimo-v2.5-1chip"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]
CONTROLS = {"int8_weights", "sink_left_out", "value_scale_left_out",
            "window_129", "thetas_swapped", "rope_whole_head"}


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def conf(man):
    return manifest.config_of(man, manifest.cell(man, CELL))


@pytest.fixture(scope="module")
def cost(conf):
    return manifest.module_of(conf, "costs_module", costs)


@pytest.fixture(scope="module")
def tiny_params(conf):
    from chipbench import control

    return control.build_params(conf["rehearsal"])


def test_the_file_holds_every_published_number_but_the_four_it_lists(
        man, conf):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json")
    assert entry["reduced"] == conf["reduced"] == REDUCED
    assert len(PUBLISHED) == 42
    differ = sorted(k for k, v in PUBLISHED.items() if conf.get(k) != v)
    assert differ == sorted(REDUCED)  # the two published lists stand whole
    assert [conf[k] for k in REDUCED] == [7, 16, 19072, 18432]
    assert conf["num_hidden_layers_published"] == 48
    assert conf["n_routed_experts_published"] == 256
    assert conf["vocab_size_published"] == 152576
    assert conf["max_position_embeddings_published"] == 1048576
    assert conf["experts_held"] == [0, 16]
    assert "16 chips" in conf["experts_deployment"]
    assert "16 chips" in conf["deployment"] and "8 ways" in conf["deployment"]
    # the floors of a cut: the leading dense layer and one whole period of
    # six, 8 experts or more, an eighth of the vocabulary
    assert conf["layer_ids"] == [0, 6, 7, 8, 9, 10, 11]
    kinds = [S if conf["hybrid_layer_pattern"][i] else F
             for i in conf["layer_ids"]]
    assert kinds == conf["layer_types"] == [F, S, S, S, S, S, F]
    assert [conf["moe_layer_freq"][i] for i in conf["layer_ids"]] == [
        0, 1, 1, 1, 1, 1, 1]
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in conf["reduced"])
    for key in REDUCED + ["sink", "value_scale", "rotated_dims",
                          "window_ends", "no_qk_norm_no_gate",
                          "attention_chunk_size", "mtp_and_towers", "norm",
                          "router", "weights", "kv_layout", "num_pages",
                          "ring", "max_seqs", "decode_attention",
                          "window_piece", "full_piece", "prefill_buckets",
                          "rehearsal"]:
        assert len(conf["assumed"][key]) > 40, key
    tol = conf["reference_tolerance"]
    assert {"min_argmax_agreement", "max_logprob_drift",
            "max_mean_logprob_drift", "max_window_attn_distance",
            "max_full_attn_distance", "why"} == set(tol)
    assert len(tol["why"]) > 400
    for name in CONTROLS:
        assert name in tol["why"], name
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_every_published_width_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", None)
    cfg = get_model(conf["preset"]).config
    widths = ref.served_widths(cfg)
    for key, value in widths.items():
        assert conf[key] == value, key
    assert list(cfg.layer_types) == conf["layer_types"]
    assert cfg.rotary_dim == ref.rotary_dims(conf) == 64
    assert len(widths) >= 21
    tiny_cfg = get_model(conf["rehearsal"]["preset"]).config
    hf = conf["rehearsal"]["hf"]
    for key, value in ref.served_widths(tiny_cfg).items():
        assert hf[key] == value, key
    assert list(tiny_cfg.layer_types) == hf["layer_types"]
    assert [w for w, *_ in ref.held_layers(hf)] == [
        k != F for k in tiny_cfg.layer_types]
    assert [m for _, m, *_ in ref.held_layers(hf)] == list(
        tiny_cfg.moe_layers)
    assert tiny_cfg.rotary_dim == ref.rotary_dims(hf)


def test_costs_on_hand_computed_bytes_and_flops(conf, cost):
    """32 rows at 13,000 tokens each: what a decode step READS, and what a
    piece multiplies: a cached token is 640 B a KV head, never a padded
    width."""
    w, rows, live = conf["weights"], 32, 32 * 13000
    assert cost.kinds(conf) == [F, S, S, S, S, S, F]
    assert (cost.full_layers(conf), cost.window_layers(conf),
            cost.expert_layers(conf)) == (2, 5, 6)
    assert cost.kv_row_bytes(conf, 4) == 2560
    assert cost.kv_row_bytes(conf, 8) == 5120
    assert cost.kv_read_bytes(conf, w, live, rows) == live * 2 * 2560
    # 2.13 GB of K and V a step in the full layers at the cell's rows
    assert abs(cost.kv_read_bytes(conf, w, live, rows) - 2.13e9) < 1e7
    in_reach = rows * 128
    assert cost.window_read_bytes(conf, w, in_reach, rows) == (
        32 * 128 * 5 * 5120)  # 21 MB a layer, 0.105 GB a step
    assert cost.pair_flops(conf, 1000) == 1000 * 64 * (2 * 192 + 2 * 128)
    assert cost.expert_bytes(conf) == 3 * 4096 * 2048 * 2
    # under even routing 32 rows x 8 of 256 touch 10.2 of the 16 held
    assert abs(cost.experts_touched(conf, rows) - 16 * (1 - (31 / 32) ** 32)
               ) < 1e-9
    assert cost.moe_experts_read_bytes(conf, w, live, rows, touched=7) == (
        7 * cost.expert_bytes(conf))
    # attention: 89.1 M a full layer, 94.4 M a window layer
    assert cost.attention_params(conf, 4) == 4096 * (
        64 * 192 + 4 * 320 + 64 * 128)
    assert abs(cost.attention_params(conf, 8) - 94.4e6) < 1e5
    dense = cost.dense_weight_bytes(conf)
    # 2 x 89.1 + 5 x 94.4 + 201.3 (layer 0's MLP) + the head's 78.1 M, in
    # bf16, and 6 float32 routers of 1.05 M
    assert abs(dense - (2 * (2 * 89.13e6 + 5 * 94.37e6 + 201.33e6 + 78.12e6)
                        + 6 * 1.049e6 * 4)) < 2e6
    step = cost.step_read_bytes(conf, w, live, rows)
    assert step == (dense + cost.moe_experts_read_bytes(conf, w, live, rows)
                    + live * 2 * 2560 + 32 * 128 * 5 * 5120)
    # 1.87 GB of dense weights, 3.09 of touched experts, 2.13 + 0.10 of K
    # and V: ~8.8 ms of a fused step at 819 GB/s
    assert 6.9e9 < step < 7.5e9


def test_the_new_metrics_name_the_cell_and_the_cell_reports_the_old_ones(
        man):
    per_layer = {m["name"]: m for m in man["per_layer"]}
    names = [m["name"] for m in man["per_layer"]]
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "output_tok_s"
        assert set(per_layer[name]) == {"name", "unit", "better", "source",
                                        "layer", "moves", "workloads"}
        assert (manifest.HERE / "layer_metrics" / f"{name}.py").is_file()
        assert callable(manifest.layer_reader(name))
        if "_share." in name:
            assert per_layer[name]["unit"] == "%"
    # appended together, in this order, after everything that was there
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == list(NEW) and at + len(NEW) == len(names)
    assert at > names.index("hbm_live_with_state_share.cmdaplus")
    layers = {m["layer"] for m in man["per_layer"][:at]}
    assert {per_layer[n]["layer"] for n in NEW} <= layers
    wanted = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    cells = [w["name"] for w in man["workloads"]]
    assert set(NEW) <= wanted
    assert {"hbm_live_share", "decode_step_ms_p50", "pipelined_launch_share",
            "mixed_step_device_ms", "mixed_steps_per_s", "mixed_busy_share",
            "device_idle_share", "kv_watermark_share"} <= wanted
    # the accepted readers that list their cells keep their lists
    assert wanted.isdisjoint({
        "decode_hbm_share", "paged_attn_hbm_share", "ssm_ms_per_step",
        "window_attn_ms_per_step.cmdaplus", "moe_experts_hbm_share.dots3",
        "sparse_tokens_attended_share", "itl_p95_ms.longgen"})
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", CELL)} == {"output_tok_s", "setup_s"}
    assert cells[-1] == CELL and man["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "longctx", "chips": 1,
        "why": man["workloads"][-1]["why"]}
    assert len(man["workloads"][-1]["why"]) <= 200
    for cell in cells[:-1]:
        assert set(NEW).isdisjoint(
            m["name"] for m in manifest.metrics_of(man, "per_layer", cell))


def test_new_readers_on_the_cells_trace(conf, cost, trace_dir):  # noqa: F811
    """The ten readers on `test_chipbench_command_a_plus.py`'s made-up
    trace (three whole fused dispatches of two steps, three whole mixed
    steps, two of each cut by the capture), with this cell's cost module and
    counts: 5 window layers of 128 keys in reach, 2 full layers. The fused
    dispatch's accepted readers (`decode_hbm_share`, `decode_{attn,mlp,head}_
    ms_per_step`) read this cell's cost module too, but the cell does not
    list them: a traced slice of `longctx` may hold no fused dispatch."""
    trace_dir()
    rows, ctx_len = 31.5, 9_000
    fused = {"kind": "decode_multi", "n_decode": 32, "tokens": 64,
             "active_pages": 32 * 190}
    mixed = {"kind": "mixed", "n_decode": 31, "n_prefill": 1,
             "prefill_tokens": 512, "tokens": 32,
             # a piece at ~10k: a whole band a window layer, x 5; the causal
             # pairs of a full layer, x 2
             "chunk_pages_read": 5 * 512 * 128,
             "chunk_pages_named": 2 * 512 * 10_000,
             "walk_pages_named": 31 * 5 * 128,
             "walk_pages_live": 31 * 5 * ctx_len,
             "moe_experts_touched": 60}
    ctx = {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1,
                    "walk_pages_named": 2 * 32 * 5 * 128,
                    "walk_pages_live": 2 * 32 * 5 * ctx_len},
                   {**mixed, "ts": 100.2}, {**mixed, "ts": 100.3}],
        "trace": {"modules": {"jit_multi_fn": {"seconds": 0.09, "count": 3}}},
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {},
        "engine_now": {"kv_total_pages": 8999, "kv_pages_watermark": 8000,
                       "state_slots": 36, "state_slots_live": 34},
        "memory": {"weights_bytes": 6_870_000_000,
                   "kv_pool_bytes": 9000 * 327_680,
                   "state_pool_bytes": 37 * 16_384_000},
        "costs": cost,
    }
    read = {name: manifest.layer_reader(name)(ctx) for name in NEW}
    assert cmdaplusscopes.decode_steps(ctx) == {
        "rows": pytest.approx(rows), "live": pytest.approx(rows * ctx_len),
        "in_reach": pytest.approx(rows * 128)}
    # the ring walk: 3 x 6 ms + 3 x 3 ms over nine steps; the page walk
    # 3 x 4 + 3 x 2
    assert read["window_attn_ms_per_step.mimo25"] == pytest.approx(3.0)
    assert read["window_attn_hbm_share.mimo25"] == pytest.approx(
        100.0 * rows * 128 * 5 * 5120 / 3e-3 / 819e9, rel=1e-6)
    assert read["full_attn_hbm_share.mimo25"] == pytest.approx(
        100.0 * rows * ctx_len * 2 * 2560 / 2e-3 / 819e9, rel=1e-6)
    # a pair: 64 heads x (2 x 192 + 2 x 128); 9 ms for 5 window layers'
    # passes, 20 ms for 2 full layers'
    pair = 64 * (2 * 192 + 2 * 128)
    assert read["window_chunk_flops_share.mimo25"] == pytest.approx(
        100.0 * 512 * 128 * pair / (9e-3 / 5) / 197e12, rel=1e-6)
    assert read["full_chunk_flops_share.mimo25"] == pytest.approx(
        100.0 * 512 * 10_000 * pair / (20e-3 / 2) / 197e12, rel=1e-6)
    assert read["moe_route_ms_per_step.mimo25"] == pytest.approx(2.0)
    assert read["moe_experts_hbm_share.mimo25"] == pytest.approx(
        100.0 * 60 * 3 * 4096 * 2048 * 2 / 12e-3 / 819e9, rel=1e-6)
    assert read["window_tokens_attended_share.mimo25"] == pytest.approx(
        100.0 * 128 / ctx_len)
    assert read["state_slots_live_share.mimo25"] == pytest.approx(
        100.0 * 34 / 36)
    # ONE generation a slot: 34 entries of 16.4 MB
    assert read["hbm_live_with_state_share.mimo25"] == pytest.approx(
        100.0 * (6_870_000_000 + 8000 * 327_680 + 34 * 16_384_000) / 16e9)
    # a fused dispatch of two steps in 30 ms over 32 rows of 190 pages less
    # half a page each: the cost module's bytes a step over 15 ms
    live = 32 * 190 * 64 - 32 * 32
    fused_read = {name: manifest.layer_reader(name)(ctx) for name in (
        "decode_hbm_share", "decode_attn_ms_per_step",
        "decode_mlp_ms_per_step", "decode_head_ms_per_step")}
    assert 0 < fused_read["decode_hbm_share"] <= 100
    assert fused_read["decode_hbm_share"] == pytest.approx(
        100.0 * cost.step_read_bytes(conf, conf["weights"], live, 32)
        / 15e-3 / 819e9, rel=1e-6)
    # by scope inside `jit_multi_fn`, over the ten steps its five launches
    # sent: `attn` 11 ms a whole dispatch and 0.5 of a cut one, `mlp` 15 of
    # each, the head 2 of each
    assert fused_read["decode_attn_ms_per_step"] == pytest.approx(3.4)
    assert fused_read["decode_mlp_ms_per_step"] == pytest.approx(7.5)
    assert fused_read["decode_head_ms_per_step"] == pytest.approx(1.0)
    for name in NEW:
        if "_share." in name:
            assert 0 < read[name] <= 100, name


def test_new_readers_give_none_where_there_is_nothing_to_read(
        conf, trace_dir):  # noqa: F811 (an empty run directory)
    """The parent commit's programs, or a run with no trace: no counters,
    no scopes, no flight records: every new reader returns None and none
    raises."""
    ctx = {"hf": conf, "weights": conf["weights"], "kernels": True,
           "peaks": None, "flight": [], "engine_now": {}, "memory": {},
           "trace": {}, "trace_info": {}, "engine": {}, "page_size": 64,
           "costs": manifest.module_of(conf, "costs_module", costs),
           "trace_dir": None, "slice": None}
    for name in NEW:
        assert manifest.layer_reader(name)(ctx) is None, name


# -- the traffic's plan -----------------------------------------------------


@pytest.mark.parametrize("first_step_rows", [1, 32])
def test_the_window_holds_long_rows_and_no_new_step_program(
        man, conf, first_step_rows):
    """`longctx` as it stands (the accepted file, unchanged) walked at this
    configuration's slots and two T buckets: every slot holds a prompt
    past 8,192 tokens (64 windows deep, its rings wrapped a dozen times)
    before the window opens, every member of the step family the plan
    meets up to the window's end is met before `ramp_tokens`, no piece
    passes what the ring leaves for a dispatch's run, and the pages the
    plan ever holds fit the pool, as does the largest demand possible."""
    mix = manifest.traffic_of(manifest.cell(man, CELL))
    assert mix["shape_seed"] == 0
    flags = conf["serve_flags"]
    at = flags.index("--prefill-buckets")
    buckets = tuple(int(x) for x in flags[at + 1:])
    assert buckets == (32, 512) and len(flags[:at]) % 2 == 0
    named = dict(zip(flags[:at:2], flags[1:at:2]))
    assert set(named) == {"--dtype", "--num-pages", "--max-seqs",
                          "--max-context"}
    pool = int(named["--num-pages"])
    cfg = get_model(conf["preset"]).config
    assert max(buckets) <= cfg.ring_run == 640 - 127
    assert cfg.ring_tokens % 64 == 0 and cfg.ring_tokens // 64 == 10
    ramp, lead = mix["ramp_tokens"], mix["ramp_lead_s"]
    # walked at the speed the chip gives this cell: up to 1,500 tokens/s
    end = ramp + 1500 * (lead + 30)
    first_seen, all_long, shortest, most_pages, delivered = _walk(
        traffic.plan(mix, 1, conf["vocab_size"]), first_step_rows, buckets,
        end)
    assert all_long + 4000 < ramp + 500 * lead
    assert shortest > 8192 == 64 * conf["sliding_window"]
    assert shortest > 12 * cfg.ring_tokens
    assert {m[1] for m in first_seen if m[0] == "mixed"} == {1, 2, 4}
    assert 7 <= len(first_seen) <= 10, first_seen
    assert max(first_seen.values()) + 10_000 < ramp, first_seen
    assert most_pages + 100 < pool
    assert int(named["--max-seqs"]) * -(-17_920 // 64) + 1 <= pool
    # ids are drawn from the slice of the vocabulary this chip holds
    plan = traffic.plan(mix, 2147480011, conf["vocab_size"])
    assert max(max(turn.new_ids) for client in plan.clients[:5]
               for turn in client) < 19072


# -- the control ------------------------------------------------------------


def test_the_control_lowers_each_of_its_ways(conf, tiny_params):
    """On the CPU, at the rehearsal's size: every named control is there,
    each plants what its name says, and int8 weights move every matrix and
    leave the router, its bias and the sinks alone."""
    import jax
    import numpy as np

    ref = manifest.module_of(conf, "reference_module", None)
    assert set(ref.CONTROLS) == CONTROLS
    assert {c["walk"]["fault"] for c in ref.CONTROLS.values()
            if "walk" in c} == set(ref.FAULTS) == CONTROLS - {"int8_weights"}
    cfg = get_model(conf["preset"]).config
    planted = {name: change(cfg) for name, change in ref.FAULTS.items()}
    assert planted["window_129"] == {"sliding_window": 129}
    assert planted["thetas_swapped"] == {"rope_theta": 1e4,
                                         "swa_rope_theta": 1e7}
    assert planted["rope_whole_head"] == {"rotary_dim": 192}
    assert planted["value_scale_left_out"] == {"attention_value_scale": 1.0}
    params = tiny_params
    lp = jax.tree.map(lambda a: a[0], params["swa"])
    low = ref.to_int8(lp)
    assert float(np.abs(np.asarray(low["wq"]) - np.asarray(lp["wq"])
                        ).max()) > 1e-4
    np.testing.assert_array_equal(low["sink"], lp["sink"])
    np.testing.assert_array_equal(low["attn_norm"], lp["attn_norm"])
    fp = jax.tree.map(lambda a: a[0], params["moe"])
    low = ref.to_int8(fp)
    np.testing.assert_array_equal(low["w_router"], fp["w_router"])
    np.testing.assert_array_equal(low["router_bias"], fp["router_bias"])
    assert float(np.abs(np.asarray(low["we_up"]) - np.asarray(fp["we_up"])
                        ).max()) > 1e-4


def test_the_harness_judges_the_tiny_program_and_a_planted_fault(conf):
    """The rehearsal's correctness half in THIS process (no server, so no
    20 s of imports and no step program's first call): the configuration's
    reference module through `chipbench.run.check_reference` on the tiny
    preset's weights: one greedy stream of 64 tokens the sound reference
    decoded, and `compare`'s own `long_path` at the rehearsal's 96 tokens
    under the INTERPRETED kernels (ring of 40 rows wrapped twice, window 5:
    the banded piece kernel under the sink, the decode walks in lane parts):
    passed, every key the harness and the ledger read is there; then
    `window_129` planted in the program at depth fails by
    `window_attn_distance` ALONE and is reported as a drift past every
    limit."""
    from chipbench import run
    from test_mimo_v2 import _seeded  # the tree at its scales, by numpy

    ref = manifest.module_of(conf, "reference_module", None)
    tol = conf["reference_tolerance"]
    params = _seeded(get_model(conf["rehearsal"]["preset"],
                               dtype="float32").config)
    hf = {**conf["rehearsal"]["hf"], "reference_tolerance": tol}
    assert hf["attention_impl"] == "pallas" and hf["long_context"] == 96
    assert 96 > 2 * get_model(hf["preset"]).config.ring_tokens
    streams = ref.control_streams(params, hf, 1234, {}, streams=1)
    sound = streams[0].pop("long_path")  # what an untouched control brings
    assert sound == {"window_attn_distance": 0.0, "full_attn_distance": 0.0}
    res = run.check_reference(params, hf, streams, tol, ref)
    assert res["passed"] is True and res["tokens"] == 64
    assert res["argmax_agreement"] == 1.0 and res["long_context"] == 96
    assert 0 < res["window_attn_distance"] < 1e-5
    assert 0 < res["full_attn_distance"] < 1e-5
    assert {"max_logprob_drift", "max_gap_to_reference_best",
            "mean_logprob_drift", "streams_s", "long_path_s",
            "tolerance"} <= set(res) and "failed_by" not in res
    # the fault at depth (without the kernels: the same routine's other
    # branch), brought beside the reference's own streams as the control does
    streams[0]["long_path"] = ref.long_path(
        params, {**hf, "attention_impl": "xla"}, 96, 1234,
        **ref.CONTROLS["window_129"]["walk"])
    res = run.check_reference(params, hf, streams, tol, ref)
    assert res["passed"] is False
    assert res["failed_by"] == ["window_attn_distance"]
    assert res["window_attn_distance"] > tol["max_window_attn_distance"]
    assert res["full_attn_distance"] < 1e-5
    assert res["mean_logprob_drift"] == float("inf")
    assert res["mean_logprob_drift_of_tokens"] == 0.0


#: `longctx`'s `rehearsal` block asks 10 clients x 6 requests, which loads 13
#: interpreted step programs (~125 s here); two clients meet the same kinds
#: of program (prefill, mixed, the fused decode) in a fraction of it
SMALL = ("from chipbench import manifest, run; t = manifest.traffic_of; "
         "manifest.traffic_of = lambda c: (lambda m: {**m, 'rehearsal': {"
         "**m['rehearsal'], 'clients': 2, 'requests_per_client': 3, "
         "'ramp_tokens': 30}})(t(c)); raise SystemExit(run.main(["
         "'--workload', 'mimo25-longctx', '--seed', '4300000019', "
         "'--seconds', '3', '--trace', '0']))")


@pytest.mark.slow  # 68 s alone (warm compile cache; 142 s cold), 140 s
# beside five other workers; the verdict's half of it is the test above
def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset mimo-v2.5-tiny, float32, `--attention-impl pallas`: prefill
    in pieces through the banded kernel under the sink over ring and pages,
    the fused decode dispatch walking ring and pages in lane parts, mixed
    steps, launch-ahead, through run in=http, and the reference agrees, the
    window and the full path at 96 tokens included. Never a result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(manifest.ROOT)}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", SMALL], cwd=manifest.ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"output_tok_s", "setup_s"}
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert all("Timeout" in f["error"] for f in notes["window"]["failures"])
    assert last["failed"] <= 2
    assert notes["serve_up"]["model"] == "mimo-v2.5-tiny"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    memory = notes["serve_up"]["memory"]
    # three full layers alone hold pages, 4 KV heads of 192 | 128; four
    # window layers, 9 + 1 slots of 40 rows of 8 KV heads: ONE generation,
    # nothing padded (float32)
    assert memory["kv_pool_bytes"] // (1024 * 4 * 4 * 320 * 4) == 3
    assert memory["state_pool_bytes"] == 4 * 10 * 40 * 8 * 320 * 4
    assert notes["correct"]["widths_as_published"] is True
    ref = notes["reference"]
    assert ref["passed"] is True and ref["tokens"] == 128
    assert ref["max_logprob_drift"] < 1e-3
    assert ref["window_attn_distance"] < 1e-5 and ref["long_context"] == 96
    assert ref["full_attn_distance"] < 1e-5
    kinds = {p["key"].split(",")[0].strip("('") for p in
             notes["programs"]["seen"]}
    assert {"mixed", "decode_multi"} <= kinds
    assert notes["window"]["preemptions"] == 0
