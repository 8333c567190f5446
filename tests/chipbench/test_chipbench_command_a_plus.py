"""`command-a-plus-1chip` and `cmdaplus-longctx` through the seam PR 26
built: the configuration file against the published numbers, the served
widths, the cost module on hand-computed bytes and FLOPs, the new per-layer
readers on a made-up trace (WHOLE dispatches only) and None where there is
nothing to read, the plan's walk under `longctx` at this cell's slots, the
control's lowerings and the cell's CPU rehearsal."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import cmdaplusscopes, costs, dots3scopes, hostspans, manifest
from chipbench import traffic
from dynamo_tpu.models.registry import get_model
from test_chipbench_deepseek_v2_lite import PEAKS, make_trace
from test_chipbench_minicpm_sala import _walk
from test_chipbench_nemotron_h import BODY, MIXED

F, S = "full_attention", "sliding_attention"
#: the catalog row's `config` (command-a-plus-05-2026's config.json, the
#: language model), every key
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": [S, S, S, F] * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144,
}
NEW = ("window_attn_ms_per_step.cmdaplus", "window_attn_hbm_share.cmdaplus",
       "full_attn_hbm_share.cmdaplus", "window_chunk_flops_share.cmdaplus",
       "full_chunk_flops_share.cmdaplus", "moe_experts_hbm_share.cmdaplus",
       "moe_route_ms_per_step.cmdaplus", "moe_shared_ms_per_step.cmdaplus",
       "window_tokens_attended_share.cmdaplus",
       "state_slots_live_share.cmdaplus",
       "hbm_live_with_state_share.cmdaplus")
CELL, CONFIG = "cmdaplus-longctx", "command-a-plus-1chip"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def conf(man):
    return manifest.config_of(man, manifest.cell(man, CELL))


@pytest.fixture(scope="module")
def cost(conf):
    return manifest.module_of(conf, "costs_module", costs)


def test_the_file_holds_every_published_number_but_the_four_it_lists(
        man, conf):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/"
        "config.json")
    assert entry["reduced"] == conf["reduced"] == REDUCED
    differ = sorted(k for k, v in PUBLISHED.items() if conf.get(k) != v)
    assert differ == sorted(REDUCED)
    assert [conf[k] for k in REDUCED] == [4, 16, 32768, 18432]
    assert conf["num_hidden_layers_published"] == 32
    assert conf["num_experts_published"] == 128
    assert conf["vocab_size_published"] == 262144
    assert conf["max_position_embeddings_published"] == 200000
    assert conf["experts_held"] == [0, 16]
    assert "8 chips" in conf["experts_deployment"]
    assert "8 chips" in conf["deployment"] and "8 ways" in conf["deployment"]
    # the floors of a cut: a whole period of four layers (no leading dense
    # one), 8 experts, an eighth of the vocabulary
    assert conf["layer_types"][:conf["num_hidden_layers"]] == [S, S, S, F]
    assert conf["num_experts"] >= 8
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in conf["reduced"])
    for key in REDUCED + ["shared_average", "shared_width", "expert_width",
                          "window_ends", "no_dense_prefix", "norm", "weights",
                          "num_pages", "ring", "max_seqs", "decode_attention",
                          "full_piece",
                          "window_piece", "prefill_buckets", "rehearsal"]:
        assert len(conf["assumed"][key]) > 40, key
    tol = conf["reference_tolerance"]
    assert {"min_argmax_agreement", "max_logprob_drift",
            "max_mean_logprob_drift", "max_window_attn_distance",
            "max_full_attn_distance", "why"} == set(tol)
    assert len(tol["why"]) > 400
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_every_published_width_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", None)
    cfg = get_model(conf["preset"]).config
    widths = ref.served_widths(cfg)
    for key, value in widths.items():
        assert conf[key] == value, key
    assert list(cfg.layer_types) == conf["layer_types"][:4]
    assert len(widths) >= 17
    tiny_cfg = get_model(conf["rehearsal"]["preset"]).config
    for key, value in ref.served_widths(tiny_cfg).items():
        assert conf["rehearsal"]["hf"][key] == value, key
    assert list(tiny_cfg.layer_types) == conf["rehearsal"]["hf"][
        "layer_types"]


def test_costs_on_hand_computed_bytes_and_flops(conf, cost):
    """32 rows at 13,000 tokens each: what a decode step READS, and what a
    piece's attention MULTIPLIES a pair."""
    w, live, rows = conf["weights"], 32 * 13_000, 32
    assert (cost.full_layers(conf), cost.sliding_layers(conf)) == (1, 3)
    # K and V of 8 KV heads of 128 in bf16: 4,096 B a token and layer
    assert cost.kv_row_bytes(conf) == 2 * 8 * 128 * 2 == 4096
    assert cost.kv_read_bytes(conf, w, live, rows) == live * 4096
    # a row past the window holds 4,096 keys in reach, 3 sliding layers
    assert cost.window_read_bytes(conf, w, 32 * 4096, rows) == (
        32 * 4096 * 3 * 4096)
    # the two walks read the same at 12,288 tokens a row: 3 x 4,096
    assert cost.window_read_bytes(conf, w, 32 * 4096, rows) == (
        cost.kv_read_bytes(conf, w, 32 * 12_288, rows))
    # a (query, key) pair: 128 heads x (128 for the score + 128 for the
    # sum), a multiply-add each
    assert cost.pair_flops(conf, 1000) == 1000 * 4 * 128 * 128
    # 16 held experts of 3 x 4096 x 4096; 32 rows x top 8 of 128 touch
    # 16 (1 - (1 - 8 / 128) ^ 32) = 13.97 of them a layer, 4 layers
    touched = 16 * (1 - (1 - 8 / 128) ** 32)
    assert cost.experts_touched(conf, 32) == pytest.approx(touched)
    expert = 3 * 4096 * 4096 * 2
    assert cost.moe_experts_read_bytes(conf, w, 0.0, 32) == pytest.approx(
        4 * touched * expert)
    assert cost.moe_experts_read_bytes(
        conf, w, 0.0, 32, touched=60) == 60 * expert
    attn = 2 * 4096 * 128 * (128 + 8)
    assert cost.attention_params(conf) == attn == 142_606_336
    dense = ((4 * (attn + 4 * 3 * 4096 * 4096 + 4096) + 4096
              + 4096 * 32768) * 2 + 4 * 4096 * 128 * 4)
    assert cost.dense_weight_bytes(conf) == dense
    assert cost.step_read_bytes(conf, w, live, rows) == pytest.approx(
        dense + 4 * touched * expert + live * 4096 + 32 * 4096 * 3 * 4096)
    # ISSUE 52's arithmetic: 10.6 GB of weights and touched experts... the
    # step's floor at 819 GB/s is 14-15 ms
    assert 13e-3 < cost.step_read_bytes(conf, w, live, rows) / 819e9 < 16e-3


# -- the readers on a made-up trace -------------------------------------------

#: one fused dispatch of two steps (30 ms)
DECODE = [
    ("%while.1", 0, 30000, "jit(multi_fn)/while:"),
    ("%fusion.2", 0, 500, BODY + "attn/qkv/dot_general:"),
    ("%paged_decode_attention.3", 500, 6000,
     BODY + "while/body/attn/window/paged_decode_attention:"),
    ("%paged_decode_attention.4", 6500, 4000,
     BODY + "attn/paged/paged_decode_attention:"),
    ("%fusion.5", 10500, 500, BODY + "attn/out/dot_general:"),
    ("%fusion.6", 11000, 1000, BODY + "mlp/moe/route/sort:"),
    ("%gmm.7", 12000, 6000, BODY + "mlp/moe/experts/gmm:"),
    ("%fusion.8", 18000, 8000, BODY + "mlp/moe/shared/dot_general:"),
    ("%fusion.9", 26000, 2000, "jit(multi_fn)/while/body/lm_head/dot:"),
]
#: one mixed step (60 ms)
CHUNK = [
    ("%ring_prefill_attention.20", 0, 9000,
     MIXED + "while/body/attn/window/ring_prefill_attention:"),
    ("%paged_decode_attention.21", 9000, 3000,
     MIXED + "while/body/attn/window/paged_decode_attention:"),
    ("%ring_prefill_attention.22", 12000, 20000,
     MIXED + "attn/flash/ring_prefill_attention:"),
    ("%paged_decode_attention.23", 32000, 2000,
     MIXED + "attn/paged/paged_decode_attention:"),
    ("%gmm.24", 34000, 12000, MIXED + "mlp/moe/experts/gmm:"),
    ("%fusion.25", 46000, 2000, MIXED + "mlp/moe/route/cumsum:"),
    ("%fusion.26", 48000, 10000, MIXED + "mlp/moe/shared/dot_general:"),
]


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """A trace of FIVE fused dispatches and FIVE mixed steps: the first
    and the last of each are what a capture cuts."""
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)

    def clear():
        hostspans._THIS_RUN.clear()
        hostspans.load.cache_clear()
        dots3scopes.load_deep.cache_clear()

    def place(decode=DECODE, chunk=CHUNK, dispatches=5):
        d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        host, modules, ops = [], [], []
        for i in range(dispatches):
            at = 10 + i * 100_000
            edge = i in (0, dispatches - 1)
            host.append(("engine.launch", at - 8, 5,
                         {"kind": "decode_multi", "k": 2}))
            host.append(("engine.launch", at + 39_990, 5,
                         {"kind": "mixed", "k": 1}))
            modules.append(("jit_multi_fn(1)", at, 30000))
            modules.append(("jit_mixed_fn(2)", at + 40_000, 60000))
            for group, lo in ((decode, at), (chunk, at + 40_000)):
                kept = group[len(group) // 2:] if edge else group
                ops += [(n, s + lo, d_, p) for n, s, d_, p in kept]
        make_trace(d / "host.xplane.pb", host=host, modules=modules, ops=ops)
        clear()

    clear()
    yield place
    clear()


def reader_ctx(conf) -> dict:
    fused = {"kind": "decode_multi", "n_decode": 32, "tokens": 64}
    mixed = {"kind": "mixed", "n_decode": 31, "n_prefill": 1,
             "prefill_tokens": 512, "tokens": 32,
             # 512 queries at ~10k: a full band a sliding layer, x 3; the
             # causal pairs of the full layer
             "chunk_pages_read": 3 * 512 * 4096,
             "chunk_pages_named": 512 * 10_000,
             # its 31 decode rows' one step, 3 sliding layers
             "walk_pages_named": 31 * 3 * 4096,
             "walk_pages_live": 31 * 3 * 12_000,
             "moe_experts_touched": 60}
    return {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1,
                    "walk_pages_named": 2 * 32 * 3 * 4096,
                    "walk_pages_live": 2 * 32 * 3 * 12_000},
                   {**mixed, "ts": 100.2}, {**mixed, "ts": 100.3}],
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {},
        "engine_now": {"kv_total_pages": 8999, "kv_pages_watermark": 8000,
                       "state_slots": 36, "state_slots_live": 33},
        "memory": {"weights_bytes": 9_466_000_000,
                   "kv_pool_bytes": 9000 * 262_144,
                   "state_pool_bytes": 37 * 56_623_104},
        "costs": manifest.module_of(conf, "costs_module", costs),
    }


def test_new_readers_on_the_cells_trace(conf, trace_dir):
    trace_dir()
    ctx = reader_ctx(conf)
    read = manifest.layer_reader
    # the WHOLE dispatches: three fused ones of two steps and three mixed
    # steps, nine steps. The ring walk: 3 x 6 ms + 3 x 3 ms over them; the
    # page walk 3 x 4 + 3 x 2
    assert read("window_attn_ms_per_step.cmdaplus")(ctx) == pytest.approx(3.0)
    at = cmdaplusscopes.decode_steps(ctx)
    assert at == {"rows": pytest.approx(31.5),
                  "live": pytest.approx(31.5 * 12_000),
                  "in_reach": pytest.approx(31.5 * 4096)}
    assert read("window_attn_hbm_share.cmdaplus")(ctx) == pytest.approx(
        100.0 * 31.5 * 4096 * 3 * 4096 / 3e-3 / 819e9, rel=1e-6)
    assert read("full_attn_hbm_share.cmdaplus")(ctx) == pytest.approx(
        100.0 * 31.5 * 12_000 * 4096 / 2e-3 / 819e9, rel=1e-6)
    # a piece's passes: the pairs of a mixed dispatch, a layer, over the
    # kernel's own time a dispatch and layer (9 ms for 3 sliding layers)
    assert read("window_chunk_flops_share.cmdaplus")(ctx) == pytest.approx(
        100.0 * 512 * 4096 * 4 * 128 * 128 / 3e-3 / 197e12, rel=1e-6)
    assert read("full_chunk_flops_share.cmdaplus")(ctx) == pytest.approx(
        100.0 * 512 * 10_000 * 4 * 128 * 128 / 20e-3 / 197e12, rel=1e-6)
    # by scope, a MIXED step (the three whole ones)
    assert read("moe_route_ms_per_step.cmdaplus")(ctx) == pytest.approx(2.0)
    assert read("moe_shared_ms_per_step.cmdaplus")(ctx) == pytest.approx(10.0)
    assert read("moe_experts_hbm_share.cmdaplus")(ctx) == pytest.approx(
        100.0 * 60 * 3 * 4096 * 4096 * 2 / 12e-3 / 819e9, rel=1e-6)
    assert read("window_tokens_attended_share.cmdaplus")(
        ctx) == pytest.approx(100.0 * 4096 / 12_000)
    assert read("state_slots_live_share.cmdaplus")(ctx) == pytest.approx(
        100.0 * 33 / 36)
    # ONE generation a slot: 33 entries of 56.6 MB
    assert read("hbm_live_with_state_share.cmdaplus")(ctx) == pytest.approx(
        100.0 * (9_466_000_000 + 8000 * 262_144 + 33 * 56_623_104) / 16e9)
    for name in NEW:
        if "_share." in name:
            assert 0 < read(name)(ctx) <= 100, name
    # dots3's readers ask for dots3's keys and leave this cell alone
    assert read("hbm_live_with_state_share.dots3")(ctx) is None
    assert read("sparse_tokens_attended_share.dots3")(ctx) is None


def test_new_readers_give_none_where_there_is_nothing_to_read(
        conf, trace_dir):
    """The parent commit's programs, or another configuration's: no
    `attn/window` in the trace, no counter in the flight records, no
    `sliding_window` in the configuration: nothing to read, no error."""
    def plain(ops):
        return [(n.replace("ring_prefill", "paged_prefill"), s, d,
                 p.replace("attn/window", "attn/paged")) for n, s, d, p in ops]

    trace_dir(plain(DECODE), plain(CHUNK))
    ctx = reader_ctx(conf)
    bare = {**ctx, "costs": costs, "hf": {"num_hidden_layers": 4},
            "engine_now": {}, "memory": {},
            "flight": [{k: v for k, v in r.items()
                        if not k.startswith(("walk_", "chunk_", "moe_"))}
                       for r in ctx["flight"]]}
    for name in NEW:
        assert manifest.layer_reader(name)(bare) is None, name
    # this configuration, a trace without the window's scope (the parent)
    for name in NEW:
        if "ms_per_step" in name or "flops" in name or "hbm_share" in name:
            assert manifest.layer_reader(name)(ctx) is None, name
    # no peaks (a CPU rehearsal)
    trace_dir()
    ctx = {**reader_ctx(conf), "peaks": None}
    for name in NEW:
        if "_share." in name and name not in (
                "window_tokens_attended_share.cmdaplus",
                "state_slots_live_share.cmdaplus"):
            assert manifest.layer_reader(name)(ctx) is None, name


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
        if "_share." in name:
            assert per_layer[name]["unit"] == "%"
    # appended together, in this order, after everything that was there
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == list(NEW) and at + len(NEW) == len(names)
    assert at > names.index("hbm_live_with_state_share.dots3")
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
        "window_attn_ms_per_step.dots3", "moe_experts_hbm_share.dots3",
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


# -- the traffic's plan -----------------------------------------------------


@pytest.mark.parametrize("first_step_rows", [1, 32])
def test_the_window_holds_long_rows_and_no_new_step_program(
        man, conf, first_step_rows):
    """`longctx` as it stands (the accepted file, unchanged) walked at this
    configuration's slots and two T buckets: every slot holds a prompt
    past 8,192 tokens (two windows deep, its rings wrapped) before the
    window opens, every member of the step family the plan meets up to the
    window's end is met before `ramp_tokens`, no piece passes what the ring
    leaves for a dispatch's run, and the pages the plan ever holds fit the
    pool, as does the largest demand possible."""
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
    assert max(buckets) <= cfg.ring_run == 4608 - 4095
    assert cfg.ring_tokens % 64 == 0 and cfg.ring_tokens // 64 == 72
    ramp, lead = mix["ramp_tokens"], mix["ramp_lead_s"]
    end = ramp + 600 * (lead + 30)
    first_seen, all_long, shortest, most_pages, delivered = _walk(
        traffic.plan(mix, 1, conf["vocab_size"]), first_step_rows, buckets,
        end)
    assert all_long + 4000 < ramp + 500 * lead
    assert shortest > 8192 == 2 * conf["sliding_window"]
    assert shortest > cfg.ring_tokens
    assert {m[1] for m in first_seen if m[0] == "mixed"} == {1, 2, 4}
    assert 7 <= len(first_seen) <= 10, first_seen
    assert max(first_seen.values()) + 10_000 < ramp, first_seen
    assert most_pages + 100 < pool
    assert int(named["--max-seqs"]) * -(-17_920 // 64) + 1 <= pool
    # ids are drawn from the slice of the vocabulary this chip holds
    plan = traffic.plan(mix, 2147480011, conf["vocab_size"])
    assert max(max(turn.new_ids) for client in plan.clients[:5]
               for turn in client) < 32768


# -- the control ------------------------------------------------------------


def test_the_control_lowers_each_of_its_ways(conf):
    """On the CPU, at the rehearsal's size: the program agrees with the
    reference on every judged query through a wrapped ring; a window one
    key short is seen by the window attention's distance alone, a rope on
    the full layer by the full attention's alone; int8 weights move every
    matrix and leave the router alone."""
    import jax
    import numpy as np

    from chipbench import control

    ref = manifest.module_of(conf, "reference_module", None)
    assert set(ref.CONTROLS) == {
        "int8_weights", "shared_summed", "short_window", "rope_full"}
    serve = conf["rehearsal"]
    hf = {**serve["hf"], "reference_tolerance": conf["reference_tolerance"]}
    params = control.build_params(serve)
    tol = conf["reference_tolerance"]
    mine = ref.long_path(params, hf, context=96)
    assert mine["window_attn_distance"] < 1e-5
    assert mine["full_attn_distance"] < 1e-5
    short = ref.long_path(params, hf, context=96, fault="short_window")
    assert short["window_attn_distance"] > tol["max_window_attn_distance"]
    assert short["full_attn_distance"] < 1e-5
    roped = ref.long_path(params, hf, context=96, fault="rope_full")
    assert roped["full_attn_distance"] > tol["max_full_attn_distance"]
    assert roped["window_attn_distance"] < 1e-5
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    low = ref.to_int8(lp)
    assert float(np.abs(np.asarray(low["we_up"]) - np.asarray(lp["we_up"])
                        ).max()) > 1e-4
    np.testing.assert_array_equal(low["w_router"], lp["w_router"])
    np.testing.assert_array_equal(low["norm"], lp["norm"])


def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset command-a-plus-tiny, float32, `--attention-impl pallas`:
    prefill in pieces through the banded kernel over ring and pages,
    the fused decode dispatch walking ring and pages, mixed steps,
    launch-ahead, through run in=http, and the reference agrees, the
    window and the full path at 96 tokens included. Never a result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(manifest.ROOT)}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", "4300000019", "--seconds", "5", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=1500)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"output_tok_s", "setup_s"}
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert all("Timeout" in f["error"] for f in notes["window"]["failures"])
    assert last["failed"] <= 2
    assert notes["serve_up"]["model"] == "command-a-plus-tiny"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    memory = notes["serve_up"]["memory"]
    # two full layers alone hold pages; four sliding layers, 9 + 1 slots
    # of 48 rows of K and V of 2 KV heads in a 128-lane tile: ONE generation
    assert memory["kv_pool_bytes"] // (1024 * 4 * 2 * 2 * 128 * 4) == 2
    assert memory["state_pool_bytes"] == 4 * 10 * 48 * 2 * 2 * 128 * 4
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
