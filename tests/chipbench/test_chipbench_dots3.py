"""`dots3-note-prev-1chip` and `dots3-longctx` through the seam PR 26 built:
the configuration file against the published numbers, the served widths,
the cost module on hand-computed bytes and FLOPs (what a step READS: the
index keys and the latent rows of the whole context in the full layers,
the windows' ring rows in the sliding ones), the new per-layer readers on
a recorded trace (WHOLE dispatches only) and None where there is nothing
to read, the plan's walk under `longctx` at this cell's slots, the
control's lowerings and the cell's CPU rehearsal."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import costs, dots3scopes, hostspans, manifest, traffic
from dynamo_tpu.models.registry import get_model
from test_chipbench_deepseek_v2_lite import PEAKS, make_trace
from test_chipbench_minicpm_sala import _walk
from test_chipbench_nemotron_h import BODY, MIXED

F, S = "full_attention", "sliding_attention"
#: the catalog row's `config` (dots3-note-prev's config.json, the language
#: model), every key
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512, "layer_types": [F] + [F, S, S, S] * 11 + [F],
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064,
}
NEW = ("index_score_ms_per_step.dots3", "index_keys_hbm_share.dots3",
       "sparse_select_ms_per_step.dots3",
       "sparse_tokens_attended_share.dots3",
       "latent_walk_roofline_share.dots3", "window_attn_ms_per_step.dots3",
       "window_attn_hbm_share.dots3", "sparse_chunk_flops_share.dots3",
       "moe_experts_hbm_share.dots3", "moe_route_ms_per_step.dots3",
       "state_slots_live_share.dots3", "hbm_live_with_state_share.dots3")
CELL, CONFIG = "dots3-longctx", "dots3-note-prev-1chip"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
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
        "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/"
        "config.json")
    assert entry["reduced"] == conf["reduced"] == REDUCED
    differ = sorted(k for k, v in PUBLISHED.items() if conf.get(k) != v)
    assert differ == sorted(REDUCED)
    assert [conf[k] for k in REDUCED] == [9, 8, 19008, 18432]
    # the file states what was published and the deployment beside it
    assert conf["num_hidden_layers_published"] == 46
    assert conf["n_routed_experts_published"] == 256
    assert conf["vocab_size_published"] == 152064
    assert conf["max_position_embeddings_published"] == 524288
    assert conf["experts_held"] == [0, 8]
    assert "32 chips" in conf["experts_deployment"]
    assert "32 chips" in conf["deployment"] and "8 ways" in conf["deployment"]
    # the floors of a cut: a whole period and four layers past the dense
    # one, 8 experts, an eighth of the vocabulary
    kinds = conf["layer_types"][:conf["num_hidden_layers"]]
    assert kinds == [F] + [F, S, S, S] * 2
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    # no width among the reduced keys, every assumed convention named
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in conf["reduced"])
    for key in ("headwise_gate", "lora_rescale", "window_ends",
                "indexer_key_norm", "indexer_rope", "indexer_weight_scale",
                "selection_rule", "weights", "num_pages", "ring",
                "decode_attention", "vocab_size"):
        assert len(conf["assumed"][key]) > 40, key
    tol = conf["reference_tolerance"]
    assert {"min_argmax_agreement", "max_logprob_drift",
            "max_mean_logprob_drift", "min_selected_tokens_agreement",
            "max_sparse_attn_distance", "max_window_attn_distance",
            "why"} == set(tol)
    assert len(tol["why"]) > 400


def test_every_published_width_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", None)
    cfg = get_model(conf["preset"]).config
    widths = ref.served_widths(cfg)
    for key, value in widths.items():
        assert conf[key] == value, key
    assert list(cfg.layer_types) == conf["layer_types"][:9]
    assert len(widths) >= 38
    tiny_cfg = get_model(conf["rehearsal"]["preset"]).config
    tiny = ref.served_widths(tiny_cfg)
    for key, value in tiny.items():
        assert conf["rehearsal"]["hf"][key] == value, key
    assert list(tiny_cfg.layer_types) == conf["rehearsal"]["hf"][
        "layer_types"]


def test_costs_on_hand_computed_bytes_and_flops(conf, cost):
    """32 rows at 13,000 tokens each: what a decode step READS and what
    its latent walk MULTIPLIES."""
    w, live, rows = conf["weights"], 32 * 13_000, 32
    assert (cost.full_layers(conf), cost.sliding_layers(conf),
            cost.expert_layers(conf)) == (3, 6, 8)
    # the latent and the rope key's 128-lane tile: 1,280 B a token, layer
    assert cost.latent_bytes_per_token(conf) == (512 + 128) * 2
    assert cost.ring_row_bytes(conf) == (1024 + 64) * 2 == 2_176
    # the walk under bits fetches EVERY page of a row, in 3 layers
    assert cost.kv_read_bytes(conf, w, live, rows) == live * 3 * 1_280
    assert cost.index_read_bytes(conf, w, live, rows) == live * 3 * 256
    assert cost.window_read_bytes(conf, w, live, rows) == (
        32 * 6 * 513 * 2_176)
    # 128 heads x (640-wide scores + 512-wide sums), a multiply-add each
    assert cost.walk_flops(conf, live) == 2 * live * 3 * 128 * (640 + 512)
    assert cost.chunk_flops(conf, 1000) == 2 * 1000 * 128 * (640 + 512)
    # at the chip's ridge: 230 FLOP a byte against 197e12 / 819e9 = 240
    assert 220 < cost.walk_flops(conf, live) / cost.kv_read_bytes(
        conf, w, live, rows) < 240
    # 8 held experts of 3 x 5120 x 1536; 32 rows x top 8 of 256 touch
    # 8 (1 - (1 - 8 / 256) ^ 32) = 5.10 of them a layer, 8 expert layers
    touched = 8 * (1 - (1 - 8 / 256) ** 32)
    assert cost.experts_touched(conf, 32) == pytest.approx(touched)
    expert = 3 * 5120 * 1536 * 2
    assert cost.moe_experts_read_bytes(conf, w, 0.0, 32) == pytest.approx(
        8 * touched * expert)
    # the device's count of a step, over its 8 expert layers
    assert cost.moe_experts_read_bytes(
        conf, w, 0.0, 32, touched=41) == 41 * expert
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
            + 128 * 128 * 5120 + 5120 * 128 + 5120 + 1024 + 512
            + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64 + 2 * 128)
    swa = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
           + 64 * 128 * 5120 + 5120 * 64 + 5120 + 1024 + 1024)
    assert cost.attention_params(conf, F) == full
    assert cost.attention_params(conf, S) == swa
    dense = ((3 * full + 6 * swa + 3 * 5120 * 13824 + 5120
              + 8 * (3 * 5120 * 1536 + 5120) + 5120 + 5120 * 19008) * 2
             + 8 * 5121 * 256 * 4)
    assert cost.dense_weight_bytes(conf) == dense
    assert cost.step_read_bytes(conf, w, live, rows) == pytest.approx(
        dense + 8 * touched * expert + live * 3 * (1_280 + 256)
        + 32 * 6 * 513 * 2_176)


# -- the readers on a recorded trace -----------------------------------------

#: one fused dispatch of two steps (30 ms)
DECODE = [
    ("%while.1", 0, 30000, "jit(multi_fn)/while:"),
    ("%fusion.2", 0, 500, BODY + "attn/qkv/dot_general:"),
    ("%paged_index_scores.3", 500, 2000,
     BODY + "attn/index/paged_index_scores:"),
    ("%fusion.4", 2500, 1000, BODY + "attn/index/dot_general:"),
    ("%fusion.5", 3500, 1000, BODY + "attn/select/while:"),
    ("%paged_decode_attention.6", 4500, 9000,
     BODY + "attn/paged/paged_decode_attention:"),
    ("%fusion.7", 13500, 500, BODY + "attn/paged/mul:"),
    ("%paged_decode_attention.8", 14000, 3000,
     BODY + "attn/window/paged/paged_decode_attention:"),
    ("%fusion.14", 17000, 1000, BODY + "attn/window/kv_update/scatter:"),
    ("%fusion.9", 18000, 500, BODY + "attn/gate/logistic:"),
    ("%fusion.10", 18500, 500, BODY + "attn/out/dot_general:"),
    ("%fusion.11", 19000, 1000, BODY + "mlp/moe/route/sort:"),
    ("%gmm.12", 20000, 6000, BODY + "mlp/moe/experts/gmm:"),
    ("%fusion.13", 26000, 2000, "jit(multi_fn)/while/body/lm_head/dot:"),
]
#: one mixed step (40 ms)
CHUNK = [
    ("%fusion.20", 0, 2000, MIXED + "attn/index/dot_general:"),
    ("%fusion.21", 2000, 1000, MIXED + "attn/select/while:"),
    ("%latent_prefill_attention.22", 3000, 20000,
     MIXED + "attn/flash/latent_prefill_attention:"),
    ("%latent_prefill_attention.23", 23000, 5000,
     MIXED + "attn/window/flash/latent_prefill_attention:"),
    ("%gmm.24", 28000, 9000, MIXED + "mlp/moe/experts/gmm:"),
    ("%fusion.25", 37000, 1000, MIXED + "mlp/moe/route/cumsum:"),
]


@pytest.fixture
def dots3_dir(tmp_path, monkeypatch):
    """A trace of FIVE fused dispatches and FIVE mixed steps: the first
    and the last of each are what a capture cuts (here: half their
    operations missing), the three between them whole."""
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)

    def clear():
        hostspans._THIS_RUN.clear()
        hostspans.load.cache_clear()  # (a second trace takes the first's path)
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
            modules.append(("jit_mixed_fn(2)", at + 40_000, 40000))
            for group, lo in ((decode, at), (chunk, at + 40_000)):
                # a cut dispatch lost the first half of its operations
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
             "prefill_tokens": 512, "tokens": 32, "active_pages": 5016,
             "ctx_min": 9100, "chunk_pages_read": 3 * 512 * 6_000,
             "chunk_pages_named": 3 * 512 * 2048,
             # its 31 decode rows' one step, counted on the device
             "walk_pages_named": 31 * 3 * 2048,
             "walk_pages_live": 31 * 3 * 10_000,
             # the held experts its 543 rows chose, 8 expert layers
             "moe_experts_touched": 60}
    return {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1, "active_pages": 5016,
                    "ctx_min": 8300,
                    # two fused steps, counted on the device, 3 full layers
                    "walk_pages_named": 2 * 32 * 3 * 2048,
                    "walk_pages_live": 2 * 32 * 3 * 10_000},
                   {**mixed, "ts": 100.2}, {**mixed, "ts": 100.3}],
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {},
        "engine_now": {"kv_total_pages": 8999, "kv_pages_watermark": 8000,
                       "state_slots": 36, "state_slots_live": 33},
        "memory": {"weights_bytes": 6_207_808_000,
                   "kv_pool_bytes": 9000 * 294_912,
                   "state_pool_bytes": 37 * 15_040_512},
        "costs": manifest.module_of(conf, "costs_module", costs),
    }


def test_new_readers_on_the_cells_trace(conf, dots3_dir, capsys):
    dots3_dir()
    ctx = reader_ctx(conf)
    read = manifest.layer_reader
    # the WHOLE dispatches: three fused ones of two steps and three mixed
    # steps, nine steps; the cut ones' halves are not read. The decode
    # rows' kernels a step of the dispatches that ran them (the recorded
    # mixed steps run other kernels under those scopes): index scores 3 x
    # 2 ms, the latent walk 3 x 9, the ring walk 3 x 3, over six steps
    assert read("index_score_ms_per_step.dots3")(ctx) == pytest.approx(1.0)
    assert read("window_attn_ms_per_step.dots3")(ctx) == pytest.approx(1.5)
    # by scope, a MIXED step (the three whole ones): select 1 ms, route 1,
    # experts 9; a fused step's 0.5 / 0.5 / 3 are not blended in
    assert read("sparse_select_ms_per_step.dots3")(ctx) == pytest.approx(1.0)
    assert read("moe_route_ms_per_step.dots3")(ctx) == pytest.approx(1.0)
    # what a step of the slice holds, from its flight records: one fused
    # dispatch of 2 steps and two mixed steps, the tokens the decode rows
    # hold counted on the device
    at = dots3scopes.decode_steps(ctx)
    assert at == {"rows": pytest.approx(31.5), "live": pytest.approx(
        (2 * 32 + 31 + 31) * 10_000 / 4), "chunk": pytest.approx(256.0)}
    live = at["live"]
    assert read("index_keys_hbm_share.dots3")(ctx) == pytest.approx(
        100.0 * live * 3 * 256 / 1e-3 / 819e9, rel=1e-6)
    assert read("window_attn_hbm_share.dots3")(ctx) == pytest.approx(
        100.0 * 31.5 * 6 * 513 * 2_176 / 1.5e-3 / 819e9, rel=1e-6)
    # the experts a mixed step's rows chose, as the device counted them
    # (60 of 8 x 8), over the 9 ms of its grouped matmuls; a record that
    # read back a rolled-back dispatch's count too is left out
    assert dots3scopes.experts_touched(ctx) == 60
    share = 100.0 * 60 * 3 * 5120 * 1536 * 2 / 9e-3 / 819e9
    assert read("moe_experts_hbm_share.dots3")(ctx) == pytest.approx(
        share, rel=1e-6)
    twice = {**ctx, "flight": ctx["flight"] + [
        {**ctx["flight"][-1], "moe_experts_touched": 120,
         "overlap_rollbacks": 1}]}
    assert read("moe_experts_hbm_share.dots3")(twice) == pytest.approx(
        share, rel=1e-6)
    # the walk: the larger of its two floors over the kernel's own 4.5 ms
    # a step; at these widths the bytes' floor is the larger one
    by_bytes = live * 3 * 1_280 / 819e9
    by_flops = 2 * live * 3 * 128 * 1152 / 197e12
    capsys.readouterr()
    assert read("latent_walk_roofline_share.dots3")(ctx) == pytest.approx(
        100.0 * max(by_bytes, by_flops) / 4.5e-3, rel=1e-6)
    bound = json.loads(capsys.readouterr().out)
    assert bound["note"] == "latent_walk_bound"
    assert bound["bound"] == ("memory" if by_bytes > by_flops else "compute")
    assert bound["kernel_ms_per_step"] == pytest.approx(4.5)
    # the chunk kernel: the mean pairs of a mixed dispatch over its 20 ms
    flops = 2 * 3 * 512 * 6_000 * 128 * 1152
    assert read("sparse_chunk_flops_share.dots3")(ctx) == pytest.approx(
        100.0 * flops / 20e-3 / 197e12, rel=1e-6)
    assert read("sparse_tokens_attended_share.dots3")(ctx) == pytest.approx(
        100.0 * 2048 / 10_000)
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {
        "note": "attended_rows", "steps_with_decode_rows": 3,
        "shortest_decode_row_tokens": 8300, "topk": 2048}
    assert read("state_slots_live_share.dots3")(ctx) == pytest.approx(
        100.0 * 33 / 36)
    # ONE generation a slot: 33 entries of 15.0 MB (6 layers x 1,088 rows
    # x (1,024 + the rope key's 128-lane tile) x 2 B), not 66
    assert read("hbm_live_with_state_share.dots3")(ctx) == pytest.approx(
        100.0 * (6_207_808_000 + 8000 * 294_912 + 33 * 15_040_512) / 16e9)
    for name in NEW:
        if "_share" in name:
            assert 0 < read(name)(ctx) <= 100, name
    # the accepted readers of a FUSED dispatch read the same trace with
    # no edit, and give None in a slice that holds no fused dispatch
    assert read("paged_attn_hbm_share")(ctx) is not None
    assert read("decode_attn_ms_per_step")(ctx) is not None
    src = (manifest.HERE / "layer_metrics"
           / "state_slots_live_share.dots3.py").read_text()
    assert 'manifest.layer_reader("state_slots_live_share")' in src


@pytest.mark.parametrize("cut", ["first-and-last", "none"])
def test_a_step_is_taken_from_whole_dispatches_only(conf, dots3_dir, cut):
    """`hostspans.fused_steps` counts a dispatch the capture cut as whole,
    so a share read through it passes what the whole dispatches give
    (PERF.md 7 m); the new readers leave the first and the last event of a
    module out, with their operations and their `k`."""
    dots3_dir()
    ctx = reader_ctx(conf)
    loaded = dots3scopes.load_deep(hostspans.newest_xplane())
    clipped = hostspans.scope_self_s(loaded, "jit_multi_fn")[
        "attn/qkv"] / sum(hostspans.fused_steps(loaded))
    found, ks = dots3scopes.whole(ctx, "jit_multi_fn")
    whole = hostspans.scope_self_s(found, "jit_multi_fn")[
        "attn/qkv"] / sum(ks)
    assert whole == pytest.approx(0.25e-3)
    # five events counted, the scope seen in three: a step reads two
    # fifths too fast, and a share of a roofline five thirds too high
    assert clipped == pytest.approx(0.25e-3 * 3 / 5)
    _, ks = dots3scopes.whole(ctx, "jit_multi_fn")
    assert ks == [2, 2, 2]
    secs, count, steps = dots3scopes.kernel_seconds(
        ctx, "paged_decode_attention", "jit_multi_fn", "attn/paged")
    assert (secs, count, steps) == (pytest.approx(27e-3), 3, 6)
    # the same kernel walking the window layers' rings is another scope's
    assert dots3scopes.kernel_seconds(
        ctx, "paged_decode_attention", "jit_multi_fn", "attn/window")[0] == (
        pytest.approx(9e-3))
    # a slice with NO fused dispatch (45 mixed steps and none fused in
    # my traced run of seed 2147480014): the decode rows' kernels are read
    # in the mixed steps
    if cut == "none":
        dots3_dir(decode=[])
        assert dots3scopes.whole(ctx, "jit_mixed_fn") is not None
        assert dots3scopes.decode_kernel_step_seconds(
            ctx, "paged_decode_attention", "attn/window") is None
        dots3_dir(decode=[], chunk=CHUNK + [
            ("%paged_decode_attention.30", 31000, 2000,
             MIXED + "attn/window/paged/paged_decode_attention:")])
        assert manifest.layer_reader("window_attn_ms_per_step.dots3")(
            ctx) == pytest.approx(2.0)
        dots3_dir(dispatches=2)  # fewer than three events: nothing whole
        assert dots3scopes.whole(ctx, "jit_multi_fn") is None
        assert manifest.layer_reader("window_attn_ms_per_step.dots3")(
            ctx) is None


def test_new_readers_give_none_where_there_is_nothing_to_read(
        conf, dots3_dir):
    """The parent commit's programs, or another configuration's: no
    `attn/window` in the trace, no counter in the flight records, no slot
    pool: nothing to read, no error."""
    def plain(ops):
        return [(n.replace("latent_prefill", "prefill"), s, d,
                 p.replace("attn/window", "attn/paged").replace(
                     "attn/index", "attn/qkv").replace(
                     "attn/select", "attn/qkv").replace(
                     "attn/gate", "attn/out")) for n, s, d, p in ops]

    dots3_dir(plain(DECODE), plain(CHUNK))
    ctx = reader_ctx(conf)
    ctx = {**ctx, "costs": costs, "hf": {"sa_config": {"topk": 2048}},
           "engine_now": {}, "memory": {},
           "flight": [{k: v for k, v in r.items()
                       if not k.startswith(("walk_", "chunk_", "moe_"))}
                      for r in ctx["flight"]]}
    for name in NEW:
        assert manifest.layer_reader(name)(ctx) is None, name
    # no peaks (a CPU rehearsal)
    dots3_dir()
    ctx = {**reader_ctx(conf), "peaks": None}
    for name in NEW:
        if "share" in name and name not in (
                "sparse_tokens_attended_share.dots3",
                "state_slots_live_share.dots3"):
            assert manifest.layer_reader(name)(ctx) is None, name
    # keye's cell keeps its own reader and this cell leaves it alone
    assert manifest.layer_reader("sparse_tokens_attended_share")(
        reader_ctx(conf)) is None


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
    assert names[at:at + len(NEW)] == list(NEW) and at + len(NEW) == len(names)
    assert at > names.index("sparse_chunk_flops_share.keye")
    layers = {m["layer"] for m in man["per_layer"][:at]}
    assert {per_layer[n]["layer"] for n in NEW} <= layers
    wanted = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    cells = [w["name"] for w in man["workloads"]]
    assert set(NEW) <= wanted
    assert {"hbm_live_share", "decode_step_ms_p50", "pipelined_launch_share",
            "mixed_step_device_ms", "mixed_steps_per_s", "mixed_busy_share",
            "device_idle_share", "kv_watermark_share"} <= wanted
    # the five accepted readers of a FUSED dispatch alone find nothing in
    # most of this cell's slices (88 % of the chip's time is mixed steps),
    # and a traced line that lacks a metric with no list is refused: each
    # carries the list of the seven cells that were there, the driver's
    # rule for a reader with nothing to read, and is otherwise as accepted
    fused_only = {"decode_hbm_share", "decode_attn_ms_per_step",
                  "decode_mlp_ms_per_step", "decode_head_ms_per_step",
                  "paged_attn_hbm_share"}
    assert wanted.isdisjoint(fused_only)
    for name in fused_only:
        assert per_layer[name]["workloads"] == cells[:-1]
        assert set(per_layer[name]) == {"name", "unit", "better", "source",
                                        "layer", "moves", "workloads"}
    assert wanted.isdisjoint({
        "ssm_ms_per_step", "sparse_pages_walked_share",
        "sparse_attn_hbm_share", "sparse_attn_hbm_share.keye",
        "index_score_ms_per_step", "sparse_tokens_attended_share",
        "moe_experts_hbm_share", "itl_p95_ms.longgen"})
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
    """`longctx` as it stands (the accepted file, unchanged) walked at
    this configuration's slots and two T buckets: every slot holds a
    prompt past 8,192 tokens (past `index_topk` four times over, and its
    rings wrapped seven times) before the window opens, every member of
    the step family the plan meets up to the window's end is met before
    `ramp_tokens`, no chunk passes what the ring leaves for a dispatch's
    run, and the pages the plan ever holds fit the pool, as does the
    largest demand possible."""
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
    assert max(buckets) <= cfg.ring_run == 1088 - 512
    ramp, lead = mix["ramp_tokens"], mix["ramp_lead_s"]
    end = ramp + 600 * (lead + 30)
    first_seen, all_long, shortest, most_pages, delivered = _walk(
        traffic.plan(mix, 1, conf["vocab_size"]), first_step_rows, buckets,
        end)
    assert all_long + 4000 < ramp + 500 * lead
    assert shortest > 8192 > 4 * conf["index_topk"] - 1
    assert shortest > 7 * cfg.ring_tokens
    assert {m[1] for m in first_seen if m[0] == "mixed"} == {1, 2, 4}
    assert 7 <= len(first_seen) <= 10, first_seen
    assert max(first_seen.values()) + 10_000 < ramp, first_seen
    assert most_pages + 100 < pool
    assert int(named["--max-seqs"]) * -(-17_920 // 64) + 1 <= pool
    # ids are drawn from the slice of the vocabulary this chip holds
    plan = traffic.plan(mix, 2147480011, conf["vocab_size"])
    assert max(max(turn.new_ids) for client in plan.clients[:5]
               for turn in client) < 19008


# -- the control ------------------------------------------------------------


def test_the_control_lowers_each_of_its_ways(conf):
    """On the CPU, at the rehearsal's size: the reference with the
    selection off reads a selection far from the reference's own; two
    cached latent rows swapped are seen by the sparse attention's distance
    alone; a window one key short by the window attention's alone; the
    program itself agrees on every judged query."""
    from chipbench import control

    ref = manifest.module_of(conf, "reference_module", None)
    assert set(ref.CONTROLS) == {
        "int8_weights", "selection_off", "swapped_rows", "short_window"}
    serve = conf["rehearsal"]
    hf = {**serve["hf"], "reference_tolerance": conf["reference_tolerance"]}
    params = control.build_params(serve)
    mine = ref.sparse_path(params, hf, context=96)
    assert mine["selected_tokens_agreement_min"] == 1.0
    assert mine["sparse_attn_distance"] < 1e-5
    assert mine["window_attn_distance"] < 1e-5
    tol = conf["reference_tolerance"]
    low = ref.lowered_sparse_path(params, hf, 96, select=False)
    assert (low["selected_tokens_agreement"]
            < tol["min_selected_tokens_agreement"] - 0.2)
    bad = ref.sparse_path(params, hf, context=96, fault="swapped_rows")
    assert bad["selected_tokens_agreement"] == 1.0
    # (8 of 96 tokens chosen here: few queries name one of the two; at the
    # cell's 2,048 of 12,288 the limit of the file separates them)
    assert bad["sparse_attn_distance"] > 100 * mine["sparse_attn_distance"]
    assert bad["sparse_attn_distance"] > 1e-3
    assert bad["window_attn_distance"] < 1e-5
    short = ref.sparse_path(params, hf, context=96, fault="short_window")
    assert short["selected_tokens_agreement"] == 1.0
    assert short["sparse_attn_distance"] < 1e-5
    assert short["window_attn_distance"] > tol["max_window_attn_distance"]
    # int8 weights move every matrix and leave the router alone
    import jax
    import numpy as np

    lp = jax.tree.map(lambda a: a[0], params["moe"])
    low = ref.to_int8(lp)
    assert float(np.abs(np.asarray(low["we_up"]) - np.asarray(lp["we_up"])
                        ).max()) > 1e-4
    np.testing.assert_array_equal(low["w_router"], lp["w_router"])
    np.testing.assert_array_equal(low["router_bias"], lp["router_bias"])


def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset dots3-tiny, float32, `--attention-impl pallas`: chunked
    prefill under chosen keys, the fused decode dispatch scoring every
    cached token and walking the latent pages under the bits, the window
    layers' rings, mixed steps, launch-ahead, through run in=http, and the
    reference agrees, the sparse and the window path at 96 tokens
    included. Never a result."""
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
    # no request fails but by its client's clock: beside five other test
    # workers a queued client may wait its 120 s out while interpreted
    # programs load
    assert all("Timeout" in f["error"] for f in notes["window"]["failures"])
    assert last["failed"] <= 2
    assert notes["serve_up"]["model"] == "dots3-tiny"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    memory = notes["serve_up"]["memory"]
    # three full layers, 1024 pages of 4 tokens: a 32-wide latent, the
    # rope key in a 128-lane tile, a 16-wide index key, and the device's
    # five counts; two sliding layers, 9 + 1 slots of 48 rows of a 48-wide
    # latent and the rope key's lane tile: ONE generation
    assert memory["kv_pool_bytes"] == (
        3 * 1024 * 4 * (32 + 128 + 16) * 4 + 5 * 4)
    assert memory["state_pool_bytes"] == 2 * 10 * 48 * (48 + 128) * 4
    assert notes["correct"]["widths_as_published"] is True
    ref = notes["reference"]
    assert ref["passed"] is True and ref["tokens"] == 128
    assert ref["max_logprob_drift"] < 1e-3
    assert ref["selected_tokens_agreement_min"] == 1.0
    assert ref["sparse_attn_distance"] < 1e-5 and ref["sparse_context"] == 96
    assert ref["window_attn_distance"] < 1e-5
    kinds = {p["key"].split(",")[0].strip("('") for p in
             notes["programs"]["seen"]}
    assert {"mixed", "decode_multi"} <= kinds
    assert notes["window"]["preemptions"] == 0
