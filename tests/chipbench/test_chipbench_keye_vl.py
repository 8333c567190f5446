"""`keye-vl2-30b-a3b-1chip` and `keye-longctx` through the seam PR 26
built: the configuration file against the published numbers, the served
widths, the cost module on hand-computed bytes (what a step READS: the
index keys and the K and V of the whole context, since the walk under
bits fetches every page), the new per-layer readers (five of their own,
three that call an existing reader), the plan's walk under `longctx`, the
control's lowerings and the cell's CPU rehearsal."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import (costs, indexscopes, manifest, sparsescopes, traffic)
from dynamo_tpu.models.registry import get_model
from test_chipbench_deepseek_v2_lite import PEAKS
from test_chipbench_minicpm_sala import _walk
from test_chipbench_nemotron_h import (  # noqa: F401 — `run_dir` a fixture
    BODY, MIXED, run_dir)

#: the catalog row's `config` (Keye-VL-2.0-30B-A3B's config.json, the
#: language model), every key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
NEW = ("index_score_ms_per_step", "index_keys_hbm_share",
       "sparse_select_ms_per_step.keye", "sparse_attn_hbm_share.keye",
       "sparse_tokens_attended_share", "moe_experts_hbm_share.keye",
       "moe_route_ms_per_step.keye", "sparse_chunk_flops_share.keye")
CELL, CONFIG = "keye-longctx", "keye-vl2-30b-a3b-1chip"


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def conf(man):
    return manifest.config_of(man, manifest.cell(man, CELL))


@pytest.fixture(scope="module")
def cost(conf):
    return manifest.module_of(conf, "costs_module", costs)


def test_the_file_holds_every_published_number_but_the_three_it_lists(
        man, conf):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "num_experts", "max_position_embeddings"]
    differ = sorted(k for k, v in PUBLISHED.items() if conf.get(k) != v)
    assert differ == sorted(conf["reduced"])
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["max_position_embeddings"]) == (8, 16, 18432)
    # the file states what was published and the deployment beside it
    assert conf["num_hidden_layers_published"] == 48
    assert conf["num_experts_published"] == 128
    assert conf["max_position_embeddings_published"] == 262144
    assert conf["experts_held"] == [0, 16]
    assert "8 chips" in conf["experts_deployment"]
    assert "8 chips" in conf["deployment"] and "six" in conf["deployment"]
    # no width among the reduced keys, every assumed convention named
    assert not {"hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "sa_config"} & set(conf["reduced"])
    for key in ("qk_norm", "indexer_key_norm", "indexer_rope",
                "indexer_weight_scale", "weights", "num_pages",
                "index_key_pool", "decode_attention"):
        assert len(conf["assumed"][key]) > 40, key
    tol = conf["reference_tolerance"]
    assert {"min_argmax_agreement", "max_logprob_drift",
            "max_mean_logprob_drift", "min_selected_tokens_agreement",
            "max_sparse_attn_distance", "why"} == set(tol)
    assert len(tol["why"]) > 400


def test_every_published_width_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", None)
    widths = ref.served_widths(get_model(conf["preset"]).config)
    for key, value in widths.items():
        assert conf[key] == value, key
    tiny = ref.served_widths(get_model(conf["rehearsal"]["preset"]).config)
    for key, value in tiny.items():
        assert conf["rehearsal"]["hf"][key] == value, key


def test_costs_on_hand_computed_bytes(conf, cost):
    """32 rows at 13,000 tokens each: what a decode step READS."""
    w, live, rows = conf["weights"], 32 * 13_000, 32
    assert cost.kv_bytes_per_token(conf) == 2 * 8 * 4 * 128 * 2 == 16_384
    assert cost.index_key_bytes_per_token(conf) == 8 * 64 * 2 == 1_024
    # the walk under bits fetches EVERY page of a row: the context's K
    # and V, not the 2,048 tokens attended
    assert cost.kv_read_bytes(conf, w, live, rows) == live * 16_384
    assert cost.index_read_bytes(conf, w, live, rows) == live * 1_024
    assert cost.walk_read_bytes(conf, w, 1000) == 1000 * 2 * 4 * 128 * 2
    # 16 held experts of 3 x 2048 x 768; 32 rows x top 8 of 128 touch
    # 16 (1 - (1 - 8 / 128) ^ 32) = 13.97 of them a layer
    touched = 16 * (1 - (1 - 8 / 128) ** 32)
    assert cost.experts_touched(conf, 32) == pytest.approx(touched)
    expert = 3 * 2048 * 768 * 2
    assert cost.moe_experts_read_bytes(conf, w, 0.0, 32) == pytest.approx(
        8 * touched * expert)
    assert cost.moe_experts_read_bytes(
        conf, w, 0.0, 32, touched=16) == 8 * 16 * expert
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2 * 2048
    index = 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64
    dense = ((8 * (attn + index) + 2048 + 2048 * 151_936) * 2
             + 8 * 2048 * 128 * 4)
    assert cost.dense_weight_bytes(conf) == dense
    assert cost.step_read_bytes(conf, w, live, rows) == pytest.approx(
        dense + 8 * touched * expert + live * (16_384 + 1_024))
    # the chunk kernel: q . k and p . v, 128 wide, 32 heads
    assert cost.chunk_flops(conf, 1000) == 4 * 1000 * 32 * 128


KEYE_OPS = [
    ("%while.1", 0, 30000, "jit(multi_fn)/while:"),
    ("%fusion.2", 0, 500, BODY + "attn/qkv/dot_general:"),
    ("%fusion.3", 500, 2000, BODY + "attn/index/gather:"),
    ("%fusion.4", 2500, 2000, BODY + "attn/index/dot_general:"),
    ("%fusion.5", 4500, 1000, BODY + "attn/select/while:"),
    ("%paged_decode_attention.6", 5500, 16000,
     BODY + "attn/paged/paged_decode_attention:"),
    ("%fusion.7", 21500, 500, BODY + "attn/paged/mul:"),
    ("%fusion.8", 22000, 500, BODY + "attn/out/dot_general:"),
    ("%fusion.9", 22500, 1000, BODY + "mlp/moe/route/sort:"),
    ("%gmm.10", 23500, 4000, BODY + "mlp/moe/experts/gmm:"),
    ("%fusion.11", 27500, 2000, "jit(multi_fn)/while/body/lm_head/dot:"),
    ("%fusion.20", 40000, 2000, MIXED + "attn/index/dot_general:"),
    ("%fusion.21", 42000, 1000, MIXED + "attn/select/while:"),
    ("%token_chunk_attention.22", 43000, 4000,
     MIXED + "attn/flash/token_chunk_attention:"),
    ("%gmm.23", 47000, 3000, MIXED + "mlp/moe/experts/gmm:"),
]


def reader_ctx(conf) -> dict:
    fused = {"kind": "decode_multi", "n_decode": 32, "tokens": 64}
    return {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1, "active_pages": 5016,
                    "ctx_min": 8300,
                    # two fused steps, counted on the device, 8 layers
                    "walk_pages_named": 2 * 32 * 8 * 2048,
                    "walk_pages_live": 2 * 32 * 8 * 10_000},
                   {"kind": "mixed", "ts": 100.2, "n_decode": 31,
                    "n_prefill": 1, "prefill_tokens": 512, "tokens": 32,
                    "active_pages": 5016, "ctx_min": 9100,
                    "chunk_pages_read": 8 * 512 * 6_000,
                    "chunk_pages_named": 8 * 512 * 2048}],
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {}, "engine_now": {},
        "memory": {},
        "costs": manifest.module_of(conf, "costs_module", costs),
    }


@pytest.fixture
def keye_dir(run_dir):  # noqa: F811
    def clear():
        sparsescopes.load_deep.cache_clear()
        indexscopes.load_deep.cache_clear()

    def place(ops):
        run_dir(ops)
        clear()

    clear()
    yield place
    clear()


def test_new_readers_on_the_cells_trace(conf, keye_dir, capsys):
    keye_dir(KEYE_OPS)
    ctx = reader_ctx(conf)
    read = manifest.layer_reader
    # two fused steps: attn/index 4 ms, attn/select 1, the walk kernel 16
    assert read("index_score_ms_per_step")(ctx) == pytest.approx(2.0)
    assert read("sparse_select_ms_per_step.keye")(ctx) == pytest.approx(0.5)
    live = 5016 * 64 - 32 * 32  # as `paged_attn_hbm_share` counts them
    assert read("index_keys_hbm_share")(ctx) == pytest.approx(
        100.0 * live * 1024 / 2e-3 / 819e9, rel=1e-6)
    # the walk reads the tokens the rows HOLD (the device's count)
    held = 32 * 8 * 10_000 * 2 * 4 * 128 * 2
    assert read("sparse_attn_hbm_share.keye")(ctx) == pytest.approx(
        100.0 * held / 8e-3 / 819e9, rel=1e-6)
    capsys.readouterr()
    assert read("sparse_tokens_attended_share")(ctx) == pytest.approx(
        100.0 * 2048 / 10_000)
    assert json.loads(capsys.readouterr().out) == {
        "note": "attended_rows", "steps_with_decode_rows": 2,
        "shortest_decode_row_tokens": 8300, "topk": 2048}
    touched = 16 * (1 - (1 - 8 / 128) ** 32)
    assert read("moe_experts_hbm_share.keye")(ctx) == pytest.approx(
        100.0 * 8 * touched * 3 * 2048 * 768 * 2 / 2e-3 / 819e9, rel=1e-6)
    assert read("moe_route_ms_per_step.keye")(ctx) == pytest.approx(0.5)
    flops = 4 * 8 * 512 * 6_000 * 32 * 128
    assert read("sparse_chunk_flops_share.keye")(ctx) == pytest.approx(
        100.0 * flops / 4e-3 / 197e12, rel=1e-6)
    for name in NEW:
        if name.endswith("_share") or "_share." in name:
            assert 0 < read(name)(ctx) <= 100, name
    # each of these IS the reader it is named after
    for name, base in (
            ("sparse_select_ms_per_step.keye", "sparse_select_ms_per_step"),
            ("moe_experts_hbm_share.keye", "moe_experts_hbm_share.nano3"),
            ("moe_route_ms_per_step.keye", "moe_route_ms_per_step")):
        assert read(name)(ctx) == read(base)(ctx)
        src = (manifest.HERE / "layer_metrics" / f"{name}.py").read_text()
        assert f'manifest.layer_reader("{base}")' in src
        assert "def read" not in src  # no copied body
    # the readers the benchmark had read the same trace with no edit: the
    # indexer and the selection inside `attn`, the walk's and the step's
    # shares from what the step READS (under 100: a stale count of the
    # 2,048 attended would read a sixth of it, one of dense pages the same)
    assert read("decode_attn_ms_per_step")(ctx) == pytest.approx(11.25)
    assert read("paged_attn_hbm_share")(ctx) == pytest.approx(
        100.0 * live * 16_384 / 8.25e-3 / 819e9, rel=1e-6)
    assert read("paged_attn_hbm_share")(ctx) < 100
    # SALA's page reader leaves a token-selecting cell alone, and this
    # one a page-selecting cell
    assert read("sparse_tokens_attended_share")(
        {**ctx, "hf": {"sparse_config": {"dense_len": 1}}}) is None


def test_new_readers_give_none_where_there_is_nothing_to_read(
        conf, keye_dir):
    """The parent commit's programs, or another configuration's: no
    `attn/index` in the trace, no counter in the flight records: nothing
    to read, no error."""
    keye_dir([(n.replace("token_chunk_attention", "fusion"), s, d,
               p.replace("attn/index", "attn/qkv").replace(
                   "attn/select", "attn/qkv").replace(
                   "mlp/moe/", "mlp/").replace("token_chunk", "chunk"))
              for n, s, d, p in KEYE_OPS])
    ctx = reader_ctx(conf)
    ctx = {**ctx, "costs": costs, "flight": [
        {k: v for k, v in r.items()
         if not k.startswith(("walk_", "chunk_"))} for r in ctx["flight"]]}
    for name in NEW:
        assert manifest.layer_reader(name)(ctx) is None, name
    # no peaks (a CPU rehearsal)
    keye_dir(KEYE_OPS)
    ctx = {**reader_ctx(conf), "peaks": None}
    for name in NEW:
        if "share" in name and name != "sparse_tokens_attended_share":
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
        if "_share" in name:
            assert per_layer[name]["unit"] == "%"
    # appended together, in this order, after everything that was there
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == list(NEW)
    assert at > names.index("hbm_live_with_state_share.sala")
    layers = {m["layer"] for m in man["per_layer"][:at]}
    assert {per_layer[n]["layer"] for n in NEW} <= layers
    # every reader file has its entry and every entry its file
    files = {p.stem for p in (manifest.HERE / "layer_metrics").glob("*.py")}
    assert files == set(names)
    wanted = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW) <= wanted
    assert {"paged_attn_hbm_share", "decode_hbm_share", "hbm_live_share",
            "decode_attn_ms_per_step", "decode_mlp_ms_per_step",
            "decode_head_ms_per_step", "pipelined_launch_share",
            "mixed_step_device_ms", "mixed_steps_per_s"} <= wanted
    assert wanted.isdisjoint({
        "ssm_ms_per_step", "sparse_pages_walked_share",
        "sparse_attn_hbm_share", "sparse_select_ms_per_step",
        "moe_experts_hbm_share", "moe_route_ms_per_step",
        "itl_p95_ms.longgen"})
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", CELL)} == {"output_tok_s", "setup_s"}
    cells = [w["name"] for w in man["workloads"]]
    assert CELL in cells and man["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "longctx", "chips": 1,
        "why": man["workloads"][cells.index(CELL)]["why"]}
    for cell in cells:
        if cell != CELL:
            assert set(NEW).isdisjoint(
                m["name"] for m in manifest.metrics_of(
                    man, "per_layer", cell))


# -- the traffic's plan -----------------------------------------------------


@pytest.mark.parametrize("first_step_rows", [1, 32])
def test_the_window_holds_long_rows_and_no_new_step_program(
        man, conf, first_step_rows):
    """`longctx` as it stands (the accepted file, unchanged) walked at
    this configuration's two T buckets: every slot holds a prompt past
    8,192 tokens (so past `topk` 2,048 four times over) before the window
    opens (`ramp_tokens`, then `ramp_lead_s` at no less than 500 tokens a
    second: the chip delivers 860-900), every member of the step family
    the plan meets up to the window's end is met before `ramp_tokens`,
    the clients outlast ramp, lead and window, and the pages the plan
    ever holds fit the pool, as does the largest demand possible."""
    mix = manifest.traffic_of(manifest.cell(man, CELL))
    assert mix["shape_seed"] == 0
    flags = conf["serve_flags"]
    at = flags.index("--prefill-buckets")
    buckets = tuple(int(x) for x in flags[at + 1:])
    assert buckets == (32, 512) and len(flags[:at]) % 2 == 0
    named = dict(zip(flags[:at:2], flags[1:at:2]))
    assert "--prefill-budget" not in named and "--prefill-chunk" not in named
    assert set(named) == {"--dtype", "--num-pages", "--max-seqs",
                          "--max-context"}
    pool = int(named["--num-pages"])
    ramp, lead = mix["ramp_tokens"], mix["ramp_lead_s"]
    end = ramp + 600 * (lead + 30)
    first_seen, all_long, shortest, most_pages, delivered = _walk(
        traffic.plan(mix, 1, 1000), first_step_rows, buckets, end)
    assert all_long + 4000 < ramp + 500 * lead
    assert shortest > 8192 > 4 * conf["sa_config"]["topk"] - 1
    assert {m[1] for m in first_seen if m[0] == "mixed"} == {1, 2, 4}
    assert {m[1] for m in first_seen if m[0] == "prefill"} == {1}
    assert 7 <= len(first_seen) <= 10, first_seen
    assert max(first_seen.values()) + 10_000 < ramp, first_seen
    assert delivered > ramp + 1000 * (lead + 30) + 90_000
    assert most_pages + 100 < pool
    assert 32 * -(-17_920 // 64) + 1 <= pool


# -- the control ------------------------------------------------------------


def test_the_control_lowers_each_of_its_ways(conf):
    """On the CPU, at the rehearsal's size: the reference with the
    selection off, half the `topk`, unit head weights reads a selection
    far from the reference's own; a fault planted in the program's cache
    is seen by the attention's distance alone; the program itself agrees
    on every judged query."""
    from chipbench import control

    ref = manifest.module_of(conf, "reference_module", None)
    assert set(ref.CONTROLS) == {
        "int8_weights", "selection_off", "topk_1024", "unit_head_weights",
        "wrong_token"}
    serve = conf["rehearsal"]
    hf = {**serve["hf"], "reference_tolerance": conf["reference_tolerance"]}
    params = control.build_params(serve)
    mine = ref.sparse_path(params, hf, context=96)
    assert mine["selected_tokens_agreement_min"] == 1.0
    assert mine["sparse_attn_distance"] < 1e-5
    tol = conf["reference_tolerance"]
    for how in ({"select": False}, {"topk": 4}, {"unit_weights": True}):
        low = ref.lowered_sparse_path(params, hf, 96, **how)
        assert (low["selected_tokens_agreement"]
                < tol["min_selected_tokens_agreement"] - 0.2), how
    bad = ref.sparse_path(params, hf, context=96, fault="wrong_token")
    assert bad["selected_tokens_agreement"] == 1.0
    assert bad["sparse_attn_distance"] > tol["max_sparse_attn_distance"]
    # int8 weights move the layer's output
    import jax
    import numpy as np

    lp = jax.tree.map(lambda a: a[0], params["layers"])
    low = ref.to_int8(lp)
    assert float(np.abs(np.asarray(low["wq"]) - np.asarray(lp["wq"])).max()
                 ) > 1e-4
    np.testing.assert_array_equal(low["w_router"], lp["w_router"])


def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset keye-vl2-tiny, float32, `--attention-impl pallas`: chunked
    prefill under token bits, the fused decode dispatch scoring every
    cached token and walking under the bits, mixed steps, launch-ahead,
    through run in=http, and the reference agrees, the sparse path at 96
    tokens included. Never a result."""
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
    assert last["failed"] == 0
    assert set(last["metrics"]) == {"output_tok_s", "setup_s"}
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert notes["serve_up"]["model"] == "keye-vl2-tiny"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    memory = notes["serve_up"]["memory"]
    # two layers, 1024 pages of 4 tokens: K and V of 2 KV heads of 16
    # cached as 128 lanes under the kernels, the index keys of both layers
    # side by side (16 wide), and the device's count, four int32
    assert memory["kv_pool_bytes"] == (
        2 * 1024 * 4 * 2 * 128 * 4 * 2 + 1024 * 4 * 16 * 4 + 4 * 4)
    assert notes["correct"]["widths_as_published"] is True
    ref = notes["reference"]
    assert ref["passed"] is True and ref["tokens"] == 128
    assert ref["max_logprob_drift"] < 1e-3
    assert ref["selected_tokens_agreement_min"] == 1.0
    assert ref["sparse_attn_distance"] < 1e-5 and ref["sparse_context"] == 96
    kinds = {p["key"].split(",")[0].strip("('") for p in
             notes["programs"]["seen"]}
    assert {"mixed", "decode_multi"} <= kinds
    assert notes["window"]["preemptions"] == 0
