"""`minicpm-sala-9b-1chip` and `sala-longctx` through the seam PR 26
built: the configuration file against the published numbers, the served
widths and scalars, the cost module on hand-computed bytes (the pages the
lists NAME, not a row's context), the new per-layer readers (three of
their own, four that call an existing reader), the plan's walk, the
control's four lowerings and the cell's CPU rehearsal."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, manifest, reference, run, sparsescopes, traffic
from dynamo_tpu.models.registry import get_model
from test_chipbench_deepseek_v2_lite import PEAKS
from test_chipbench_nemotron_h import (  # noqa: F401 — `run_dir` a fixture
    BODY, MIXED, run_dir)

MIXERS = ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] + [
    "lightning-attn"] * 6 + ["minicpm4"] * 2 + ["lightning-attn"] * 4 + [
    "minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 3
#: MiniCPM-SALA's config.json as published (the catalog's row, every key)
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": MIXERS, "num_attention_heads": 32,
    "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
}
NEW = ("sparse_select_ms_per_step", "sparse_attn_hbm_share",
       "sparse_pages_walked_share", "ssm_ms_per_step.sala",
       "ssm_scan_hbm_share.sala", "state_slots_live_share.sala",
       "hbm_live_with_state_share.sala")
CELL, CONFIG = "sala-longctx", "minicpm-sala-9b-1chip"


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
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "mixer_types", "max_position_embeddings"]
    assert len(MIXERS) == 32 and MIXERS.count("minicpm4") == 8
    differ = sorted(k for k, v in PUBLISHED.items()
                    if k not in conf or conf[k] != v)
    assert differ == sorted(conf["reduced"])
    assert conf["num_hidden_layers"] == 16
    assert conf["max_position_embeddings"] == 18432
    assert conf["layer_indices"] == list(range(9, 25))
    assert conf["mixer_types"] == MIXERS[9:25]
    assert conf["mixer_types"].count("minicpm4") == 4  # the published 1 : 3
    assert (conf["num_hidden_layers_published"],
            conf["max_position_embeddings_published"],
            conf["mixer_types_published"]) == (32, 524288, MIXERS)
    assert conf["torch_dtype"] == "bfloat16"
    # no width among the reduced: every one as published
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "lightning_nh",
                "lightning_nkv", "lightning_head_dim", "vocab_size",
                "scale_emb", "scale_depth", "dim_model_base",
                "mup_denominator"):
        assert conf[key] == PUBLISHED[key], key
    assert conf["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert "two-stage pipeline over depth" in conf["deployment"]
    assert "layers 9-24" in conf["deployment"]
    assert "What the cut distorts" in conf["deployment"]
    for key in (*conf["reduced"][::2], "sparse_config", "lightning_decay",
                "lightning_norms", "rule_by_query_position", "weights",
                "ssm_state_dtype", "num_pages", "state_slots", "max_seqs"):
        assert key in conf["assumed"], key
    assert "MiniCPM4's published sparse_config" in conf["assumed"][
        "sparse_config"]
    assert "1 / (c sqrt(fan_in))" in conf["assumed"]["weights"]
    tol = conf["reference_tolerance"]
    assert set(tol) >= {
        "min_argmax_agreement", "max_logprob_drift", "max_mean_logprob_drift",
        "max_ssm_state_distance", "min_selected_pages_agreement",
        "max_sparse_attn_distance", "why"}
    assert len(tol["why"]) > 500
    cells = [w for w in man["workloads"] if w["config"] == CONFIG]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "longctx", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # appended: the last configuration and the last cell of their lists
    assert man["configs"][-1] is entry and man["workloads"][-1] is cell


def test_every_published_width_and_scalar_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", reference)
    assert ref.__file__ == str(
        manifest.HERE / "references" / "minicpm_sala.py")
    cfg = get_model(conf["preset"], dtype="bfloat16",
                    attention_impl="pallas").config
    widths = run.served_widths(cfg, ref)
    assert set(widths) >= {
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "mixer_types", "layer_indices", "num_attention_heads",
        "num_key_value_heads", "head_dim", "lightning_nh",
        "lightning_head_dim", "vocab_size", "scale_emb", "scale_depth",
        "dim_model_base", "mup_denominator", "sparse_config"}
    assert all(k in conf and widths[k] == conf[k] for k in widths)
    assert cfg.attention_impl == "pallas" and cfg.dtype == jnp.bfloat16
    assert cfg.num_layers == 16 and not cfg.attn_cfg.use_rope
    # the serve flags and no other: the engine's own step budget (four
    # chunks) and chunk (512) serve the cell, a tail piece padded to 32 or
    # to 512 (fewer step programs: the file's `assumed`); a page is a block
    assert conf["serve_flags"] == [
        "--dtype", "bfloat16", "--num-pages", "9000", "--max-seqs", "32",
        "--max-context", "18432", "--prefill-buckets", "32", "512"]
    tiny = get_model(conf["rehearsal"]["preset"]).config
    hf = conf["rehearsal"]["hf"]
    small = run.served_widths(tiny, ref)
    assert all(k in hf and small[k] == hf[k] for k in small)


def test_costs_on_hand_computed_bytes(conf, cost):
    assert cost.__file__ == str(manifest.HERE / "costs_minicpm_sala.py")
    w = {"itemsize": 2}
    # a row's state: 12 lightning layers x 32 x 128 x 128 x 4 B
    assert cost.ssm_state_bytes_per_row(conf) == 12 * 2_097_152 == 25_165_824
    assert cost.ssm_state_bytes(conf, w, 0.0, 32) == 2 * 32 * 25_165_824
    # a token's K and V: 4 sparse layers x 2 KV heads x 128 x 2 x 2 B
    assert cost.kv_bytes_per_token(conf) == 4096
    # 32 rows at 12,000 tokens each: the walk reads 63.5 pages a list, NOT
    # the 187.5 a row holds
    live = 32 * 12_000.0
    assert cost.walked_tokens(conf, live, 32) == 32 * 63.5 * 64
    assert cost.kv_read_bytes(conf, w, live, 32) == 32 * 63.5 * 64 * 4096
    assert cost.kv_read_bytes(conf, w, live, 32) < 0.35 * live * 4096
    # short rows beside long ones: the mean over-counts (the docstring's
    # case: 88k tokens read), which is why the new kernel's share takes
    # the device's own count of pages instead
    mixed = 12 * 600.0 + 20 * 13_000.0
    assert cost.walked_tokens(conf, mixed, 32) == 32 * 63.5 * 64 > (
        12 * 600 + 20 * 63.5 * 64)
    # a page a KV head and a layer: 64 tokens x 128 x (K, V) x 2 B
    assert cost.walk_bytes(conf, w, 1) == 32_768
    assert cost.walk_bytes(conf, w, 32 * 8 * 64) == 32 * 64 * 64 * 4096
    # under dense_len a row reads all it has; no rows, nothing
    assert cost.kv_read_bytes(conf, w, 32 * 5000.0, 32) == 32 * 5000 * 4096
    assert cost.kv_read_bytes(conf, w, 0.0, 0) == 0.0
    # the compressed keys: one every 16 tokens, 4 layers x 2 KV heads x 128
    assert cost.compressed_read_bytes(conf, w, live, 32) == (
        live / 16 * 4 * 2 * 128 * 2)
    assert cost.compressed_read_bytes(conf, w, 32 * 5000.0, 32) == 0.0
    p = cost.layer_weight_params(conf)
    assert p["minicpm4"] == 3 * 4096 * 4096 + 2 * 4096 * 256 + 256
    assert p["lightning-attn"] == 5 * 4096 * 4096 + 384
    assert p["mlp"] == 3 * 4096 * 16384 == 201_326_592
    layers = 4 * (p["minicpm4"] + p["mlp"] + 8192) + 12 * (
        p["lightning-attn"] + p["mlp"] + 8192)
    head = 4096 * 73448
    assert cost.weight_bytes(conf) == 2 * (layers + 4096 + head)
    assert cost.weight_bytes(conf, with_embed=True) == 2 * (
        layers + 4096 + 2 * head)
    assert 10.07e9 < cost.weight_bytes(conf, with_embed=True) < 10.09e9
    # the served tree IS that many bytes
    import jax

    adapter = get_model(conf["preset"], dtype="bfloat16")
    tree = jax.eval_shape(lambda: adapter.init_params(jax.random.key(0)))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        tree)) == cost.weight_bytes(conf, with_embed=True)
    assert cost.step_read_bytes(conf, w, live, 32) == (
        cost.weight_bytes(conf) + 32 * 63.5 * 64 * 4096
        + live / 16 * 2048 + 2 * 32 * 25_165_824)
    # a step's state (1.61 GB) is three times its selected pages (0.53)
    assert 1.6e9 < cost.ssm_state_bytes(conf, w, 0, 32) < 1.62e9
    assert 0.52e9 < cost.kv_read_bytes(conf, w, live, 32) < 0.54e9


#: one fused dispatch of two steps (the fixture's module is 30 ms long: an
#: operation counts if it starts inside), one mixed step (10 ms)
SALA_OPS = [
    ("%while.1", 0, 36000, "jit(multi_fn)/while:"),
    ("%fusion.2", 0, 2000, BODY + "attn/ssm/in_proj/dot_general:"),
    ("%kernel.3", 2000, 5000, BODY + "attn/ssm/scan/ssm_decode_step:"),
    ("%fusion.4", 7000, 1000, BODY + "attn/ssm/out/dot_general:"),
    ("%fusion.5", 8000, 500, BODY + "attn/qkv/dot_general:"),
    ("%fusion.6", 8500, 1500, BODY + "attn/select/sort:"),
    ("%fusion.7", 10000, 500, BODY + "attn/select/gather:"),
    ("%paged_decode_attention.8", 10500, 3000,
     BODY + "attn/paged/paged_decode_attention:"),
    ("%fusion.9", 13500, 500, BODY + "attn/paged/mul:"),
    ("%fusion.10", 14000, 500, BODY + "attn/out/dot_general:"),
    ("%fusion.11", 14500, 11500, BODY + "mlp/dot_general:"),
    ("%fusion.12", 26000, 6000, "jit(multi_fn)/while/body/lm_head/dot:"),
    ("%fusion.20", 40000, 2000, MIXED + "attn/select/sort:"),
    ("%fusion.21", 42000, 4000, MIXED + "attn/flash/dot_general:"),
    ("%fusion.22", 46000, 4000, MIXED + "mlp/dot_general:"),
]


def reader_ctx(conf) -> dict:
    fused = {"kind": "decode_multi", "n_decode": 32, "tokens": 64}
    return {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1, "active_pages": 6400,
                    "ctx_min": 8300,
                    # two fused steps, counted on the device
                    "walk_pages_named": 2 * 32 * 8 * 64,
                    "walk_pages_live": 2 * 32 * 8 * 200},
                   {"kind": "mixed", "ts": 100.2, "n_decode": 31,
                    "n_prefill": 1, "prefill_tokens": 512, "tokens": 32,
                    "active_pages": 6400, "ctx_min": 9100}],
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {},
        "engine_now": {"state_slots": 36, "state_slots_live": 34,
                       "kv_total_pages": 8999, "kv_pages_watermark": 7000},
        "memory": {"weights_bytes": 10_080_000_000,
                   "kv_pool_bytes": 9000 * 270_336,
                   "state_pool_bytes": 74 * 25_165_824},
        "costs": manifest.module_of(conf, "costs_module", costs),
    }


@pytest.fixture
def sala_dir(run_dir):  # noqa: F811
    def place(ops):
        run_dir(ops)
        sparsescopes.load_deep.cache_clear()

    sparsescopes.load_deep.cache_clear()
    yield place
    sparsescopes.load_deep.cache_clear()


def test_new_readers_on_the_cells_trace(conf, cost, sala_dir, capsys):
    sala_dir(SALA_OPS)
    ctx = reader_ctx(conf)
    read = manifest.layer_reader
    # two fused steps: attn/select 2 ms, the walk kernel's own events 3
    assert read("sparse_select_ms_per_step")(ctx) == pytest.approx(1.0)
    # the new kernel's bytes are the pages the device counted: 64 whole
    # pages a list in each of the two fused steps
    named = 32 * 8 * 64 * 32_768
    assert read("sparse_attn_hbm_share")(ctx) == pytest.approx(
        100.0 * named / 1.5e-3 / 819e9, rel=1e-6)
    capsys.readouterr()
    assert read("sparse_pages_walked_share")(ctx) == pytest.approx(32.0)
    assert json.loads(capsys.readouterr().out) == {
        "note": "walk_rows", "steps_with_decode_rows": 2,
        "shortest_decode_row_tokens": 8300,
        "shortest_at_the_windows_first_step": 8300, "dense_len": 8192}
    walked = 32 * 63.5 * 64 * 4096  # the rows' mean context is 12.8k
    assert read("ssm_ms_per_step.sala")(ctx) == pytest.approx(4.0)
    state = 2 * 32 * 25_165_824
    assert read("ssm_scan_hbm_share.sala")(ctx) == pytest.approx(
        100.0 * state / 2.5e-3 / 819e9, rel=1e-6)
    assert read("state_slots_live_share.sala")(ctx) == pytest.approx(
        100.0 * 34 / 36)
    live = 10.08e9 + 7000 * 270_336 + 2 * 34 * 25_165_824
    assert read("hbm_live_with_state_share.sala")(ctx) == pytest.approx(
        100.0 * live / 16e9)
    for name in NEW[1:]:
        assert 0 < read(name)(ctx) <= 100, name
    # each `.sala` reader IS the reader it is named after
    for name in NEW[3:]:
        base = name.rsplit(".", 1)[0]
        assert read(name)(ctx) == read(base)(ctx)
        src = (manifest.HERE / "layer_metrics" / f"{name}.py").read_text()
        assert f'manifest.layer_reader("{base}")' in src
        assert "def read" not in src  # no copied body
    # the readers the benchmark had read the same trace with no edit: the
    # selection and both mixers inside `attn`, and the walk's share from
    # the pages the lists name (the whole context would read 3 times it)
    assert read("decode_attn_ms_per_step")(ctx) == pytest.approx(7.25)
    assert read("decode_mlp_ms_per_step")(ctx) == pytest.approx(5.75)
    assert read("decode_head_ms_per_step")(ctx) == pytest.approx(3.0)
    assert read("paged_attn_hbm_share")(ctx) == pytest.approx(
        100.0 * walked / 1.75e-3 / 819e9, rel=1e-6)
    assert read("paged_attn_hbm_share")(ctx) < read(
        "sparse_attn_hbm_share")(ctx) < 100


def test_new_readers_give_none_where_there_is_nothing_to_read(
        conf, sala_dir):
    """The parent commit's programs, or another configuration's: no
    `attn/select` in the trace, no counter in the flight records, no
    state pool: nothing to read, no error."""
    sala_dir([(n, s, d, p.replace("attn/select", "attn/qkv").replace(
        "attn/ssm/scan", "attn/paged").replace("attn/ssm/", "attn/"))
        for n, s, d, p in SALA_OPS])
    ctx = reader_ctx(conf)
    ctx = {**ctx, "engine_now": {"kv_total_pages": 100,
                                 "kv_pages_watermark": 50},
           "memory": {"weights_bytes": 1, "kv_pool_bytes": 2},
           "flight": [{k: v for k, v in r.items()
                       if not k.startswith("walk_")} for r in ctx["flight"]]}
    for name in NEW:
        assert manifest.layer_reader(name)(ctx) is None, name
    # no trace at all, and no peaks (a CPU rehearsal)
    sala_dir(SALA_OPS)
    ctx = {**reader_ctx(conf), "peaks": None}
    for name in ("sparse_attn_hbm_share", "ssm_scan_hbm_share.sala",
                 "hbm_live_with_state_share.sala"):
        assert manifest.layer_reader(name)(ctx) is None, name


def test_the_new_metrics_name_the_cell_and_the_cell_reports_the_old_ones(man):
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "output_tok_s"
        assert set(per_layer[name]) == {"name", "unit", "better", "source",
                                        "layer", "moves", "workloads"}
        assert (manifest.HERE / "layer_metrics" / f"{name}.py").is_file()
    names = [m["name"] for m in man["per_layer"]]
    assert names[-len(NEW):] == list(NEW)  # appended together, at the end
    layers = {m["layer"] for m in man["per_layer"][: -len(NEW)]}
    assert {per_layer[n]["layer"] for n in NEW} <= layers
    assert per_layer["sparse_attn_hbm_share"]["unit"] == "%"
    # every reader file has its entry
    files = {p.stem for p in (manifest.HERE / "layer_metrics").glob("*.py")}
    assert files == set(names)
    wanted = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW) <= wanted
    assert {"paged_attn_hbm_share", "decode_hbm_share", "hbm_live_share",
            "decode_attn_ms_per_step", "decode_mlp_ms_per_step",
            "decode_head_ms_per_step", "pipelined_launch_share",
            "mixed_step_device_ms", "mixed_steps_per_s"} <= wanted
    assert wanted.isdisjoint({
        "ssm_ms_per_step", "ssm_scan_hbm_share", "ssm_ms_per_step.falconh1",
        "state_slots_live_share", "hbm_live_with_state_share",
        "moe_experts_hbm_share", "itl_p95_ms.longgen"})
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", CELL)} == {"output_tok_s", "setup_s"}
    for cell in ("qwen2-longgen", "phi3-chat-closed", "dsv2lite-docgen",
                 "nano3-chat-churn", "falconh1-longdoc"):
        assert set(NEW).isdisjoint(m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell))


# -- the traffic's plan -----------------------------------------------------


def test_the_traffic_is_the_issues(man):
    mix = manifest.traffic_of(manifest.cell(man, CELL))
    assert (mix["loop"], mix["clients"], mix["requests_per_client"]) == (
        "closed", 40, 8)
    assert mix["prompt_tokens"] == {
        "dist": "uniform_int", "min": 8193, "max": 16384,
        "why": mix["prompt_tokens"]["why"]}
    assert mix["output_tokens"] == {
        "dist": "uniform_int", "min": 768, "max": 1536,
        "why": mix["output_tokens"]["why"]}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert mix["phase_first_request"] is True
    assert 30_000 <= mix["ramp_tokens"] <= 60_000
    assert mix["ramp_lead_s"] == 30.0
    for key in ("why", "requests_why", "ramp_why"):
        assert len(mix[key]) > 100
    assert "rehearsal" in mix
    plan = traffic.plan(mix, 3_000_000_019, 73448)
    assert len(plan.clients) == 40
    heads = [tuple(t.new_ids[:64]) for c in plan.clients for t in c]
    assert len(set(heads)) == len(heads)  # fresh ids: nothing shared
    firsts = [len(c[0].new_ids) for c in plan.clients]
    assert set(firsts) == {mix["first_prompt_tokens"]["value"]} == {32}
    assert 32 * firsts[0] <= 4 * 512
    # every later prompt stands past dense_len before its first token
    assert min(len(t.new_ids) for c in plan.clients for t in c[1:]) > 8192
    assert max(len(t.new_ids) + t.max_tokens
               for c in plan.clients for t in c) <= 17_920 < 18_432


def _walk(plan, first_step_rows, buckets, stop_at):
    """tests/chipbench/test_chipbench_falcon_h1.py's coarse simulation of
    the closed loop (32 slots, the scheduler's piece rule under the
    default step budget of four chunks, the engine's grouping under the
    configuration's T buckets) up to `stop_at` delivered tokens: which
    (kind, piece rows, T bucket, sampled) members are met beside a full
    batch and at how many delivered tokens (this family has one program
    for first and later chunks), the tokens delivered when every slot
    first holds a prompt past `dense_len`, the shortest prompt among the
    rows that decode from then on, the most pages ever held, and what
    the plan delivers in all."""
    import collections

    bucket = lambda t: next(b for b in buckets if b >= t)  # noqa: E731
    nxt, queue = [0] * 40, collections.deque(range(40))
    running, delivered, first_seen, most_pages, step = [], 0, {}, 0, 0
    all_long, shortest = None, 1 << 30
    while queue or running:
        step += 1
        cap = first_step_rows if step == 1 else 32
        while queue and len(running) < cap:
            c = queue.popleft()
            turn = plan.clients[c][nxt[c]]
            running.append({"c": c, "p": len(turn.new_ids), "done": 0,
                            "out": turn.max_tokens, "want": turn.max_tokens})
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
        if pieces and delivered < stop_at:
            groups: dict = {}
            for r, t in pieces:
                groups.setdefault(bucket(t), []).append((r, t))
            members = []
            if n_dec:
                members.append(("mixed", groups.pop(max(groups))))
            members += [("prefill", g) for g in groups.values()]
            for kind, g in members:
                n = 1
                while n < len(g):
                    n *= 2
                member = (kind, n, bucket(max(t for _r, t in g)),
                          any(r["done"] + t >= r["p"] for r, t in g))
                if n_dec > 16:  # the steady state's 32-row programs
                    first_seen.setdefault(member, delivered)
        steps = 1 if pieces else 8
        fed = {id(r) for r, _t in pieces}
        for r, t in pieces:
            r["done"] += t
        if all_long is None and len(running) == 32 and all(
                r["p"] > 8192 for r in running):
            all_long = delivered
        if all_long is not None and delivered < stop_at:
            shortest = min([shortest] + [
                r["p"] for r in running if r["done"] >= r["p"]])
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
        most_pages = max(most_pages, sum(
            -(-(r["p"] + r["want"] - r["out"]) // 64) for r in running))
    return first_seen, all_long, shortest, most_pages, delivered


@pytest.mark.parametrize("first_step_rows", [1, 32])
def test_the_window_holds_long_rows_and_no_new_step_program(
        man, conf, first_step_rows):
    """The plan is the same in every run (`shape_seed` 0, the generator's
    default: not picked), so which prompts prefill side by side is a
    property of the file. Walked at the configuration's two T buckets: every slot
    holds a prompt past `dense_len` well before the window opens
    (`ramp_tokens`, then `ramp_lead_s` at no less than 400 tokens a
    second: the chip delivers 510-520), so every row that decodes inside
    the window stands past 8,192 tokens; every member of the step family
    the plan meets up to the window's end (at 600 a second) is met before
    `ramp_tokens`, whether the first request arrives alone or with the
    others; the clients outlast ramp, lead and window; the pages the plan
    ever holds fit the pool, as does the largest demand possible."""
    mix = manifest.traffic_of(manifest.cell(man, CELL))
    assert mix["shape_seed"] == 0
    flags = conf["serve_flags"]
    at = flags.index("--prefill-buckets")
    buckets = tuple(int(x) for x in flags[at + 1:])
    assert buckets == (32, 512) and len(flags[:at]) % 2 == 0
    named = dict(zip(flags[:at:2], flags[1:at:2]))
    assert "--prefill-budget" not in named and "--prefill-chunk" not in named
    pool = int(named["--num-pages"])
    ramp, lead = mix["ramp_tokens"], mix["ramp_lead_s"]
    end = ramp + 600 * (lead + 30)
    first_seen, all_long, shortest, most_pages, delivered = _walk(
        traffic.plan(mix, 1, 1000), first_step_rows, buckets, end)
    assert all_long + 4000 < ramp + 400 * lead
    assert shortest > 8192
    assert {m[1] for m in first_seen if m[0] == "mixed"} == {1, 2, 4}
    assert {m[1] for m in first_seen if m[0] == "prefill"} == {1}
    assert 7 <= len(first_seen) <= 10, first_seen
    assert max(first_seen.values()) + 10_000 < ramp, first_seen
    # ramp + lead and window at 1,000 tokens a second, and as much again
    assert delivered > ramp + 1000 * (lead + 30) + 90_000
    assert most_pages + 100 < pool
    # and whatever the plan: 32 rows at the longest context a request has
    assert 32 * -(-17_920 // 64) + 1 <= pool


# -- the control ------------------------------------------------------------


def test_the_control_lowers_each_of_its_five_ways(conf):
    """On the CPU, at the rehearsal's size: the reference with int8
    weights, a bfloat16 state, the selection off and the compressed keys
    at another stride each READ differently from the reference as it
    stands, and a fault planted in the program's walk reads as a distance
    and as nothing else (the chip decides whether each fails by the
    file's limits)."""
    ref = manifest.module_of(conf, "reference_module", reference)
    assert set(ref.CONTROLS) == {"bf16_state", "int8_weights",
                                 "selection_off", "compressed_stride_32",
                                 "wrong_page"}
    hf = conf["rehearsal"]["hf"]
    adapter = get_model(hf["preset"])
    import jax

    params = adapter.init_params(jax.random.key(0))
    ids = np.random.default_rng(0).integers(10, 256, 60)
    at = np.arange(40, 60)
    want = ref.log_probs(params, hf, ids, at)
    lowered = {
        "int8_weights": dict(lower=ref.to_int8),
        "bf16_state": dict(state_dtype=jnp.dtype("bfloat16")),
        "selection_off": dict(select=False),
        "compressed_stride_32": dict(compress_stride=2),
    }
    for name, how in lowered.items():
        got = ref.log_probs(params, hf, ids, at, **how)
        assert np.abs(got - want).max() > 1e-3, name
    # the sparse controls bring their readings of the sparse path: dense
    # attention names three to four times the reference's blocks, another
    # stride other blocks; neither has a query left whose selection is the
    # reference's at every KV head and layer
    for how in (dict(select=False), dict(compress_stride=2)):
        res = ref.lowered_sparse_path(params, hf, 96, **how)
        assert res["selected_pages_agreement"] < 0.97, how
        assert res["sparse_selection_matched_share"] < 0.9, how
    # and a stream that brings them fails `compare` by them alone
    streams = ref.control_streams(
        params, hf, 1, {"sparse": {"select": False}}, prompt_len=8,
        out_len=4, streams=1)
    res = ref.compare(params, {**hf, "reference_tolerance": conf[
        "reference_tolerance"]}, streams)
    # (a lowered reference under its own selection computes the same
    # sums: the distance reads 0)
    assert res["failed_by"] == ["selected_pages_agreement"]
    assert res["sparse_attn_distance"] == 0.0
    assert res["mean_logprob_drift"] == float("inf")
    assert res["mean_logprob_drift_of_tokens"] < 1e-5
    # the planted fault: the PROGRAM's own path with blocks 0 and 1 of the
    # pool in each other's place. Every selection is still the
    # reference's; the walk's output is not, and `compare` fails by that
    # alone, through the same limit the program is held to
    full = {**hf, "reference_tolerance": conf["reference_tolerance"],
            "sparse_context": 96}
    clean = ref.sparse_path(params, full, 96, 1)
    assert clean["sparse_attn_distance"] < 1e-5
    streams = ref.control_streams(
        params, full, 1, ref.CONTROLS["wrong_page"], prompt_len=8,
        out_len=4, streams=1)
    planted = streams[0]["sparse_path"]
    assert planted["selected_pages_agreement_min"] == 1.0
    assert planted["sparse_attn_distance"] > 0.05
    res = ref.compare(params, full, streams)
    assert res["failed_by"] == ["sparse_attn_distance"]
    assert res["mean_logprob_drift"] == float("inf")


def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset minicpm-sala-tiny, float32, `--attention-impl pallas`:
    chunked prefill under the block mask, the fused decode dispatch
    selecting pages inside the walk and advancing the lightning states,
    mixed steps, launch-ahead, through run in=http, and the reference
    agrees, the sparse path at 96 tokens included. Never a result."""
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
    assert notes["serve_up"]["model"] == "minicpm-sala-tiny"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    memory = notes["serve_up"]["memory"]
    # 8 + 1 slots, two generations and the null entries, two lightning
    # layers of 4 heads x 16 x 16 float32
    assert memory["state_pool_bytes"] == 20 * 2 * 4 * 16 * 16 * 4
    # three sparse layers, 1024 pages of 4 tokens x 2 KV heads of 16
    # cached as 128 lanes under the kernels: K, V and 4 compressed keys;
    # and the device's count of what its walks read, two int32
    assert memory["kv_pool_bytes"] == (
        3 * 1024 * 2 * 128 * 4 * (2 * 4 + 4) + 2 * 4)
    assert notes["correct"]["widths_as_published"] is True
    ref = notes["reference"]
    assert ref["passed"] is True and ref["tokens"] == 128
    assert ref["max_logprob_drift"] < 1e-3
    assert ref["ssm_state_distance"] < 1e-6
    assert ref["selected_pages_agreement_min"] == 1.0
    assert ref["sparse_selection_matched_share"] == 1.0
    assert ref["sparse_attn_distance"] < 1e-5 and ref["sparse_context"] == 96
    kinds = {p["key"].split(",")[0].strip("('") for p in
             notes["programs"]["seen"]}
    assert {"mixed", "decode_multi"} <= kinds
    assert notes["window"]["preemptions"] == 0
