"""`deepseek-v2-lite-1chip` and `dsv2lite-docgen` through the seam PR 26
built (tests/chipbench/test_chipbench_config_seam.py): the configuration
file against the published numbers, its reference and cost modules found
by file, the cost module on hand-computed bytes, each new per-layer
reader on a made-up trace, and the cell's CPU rehearsal."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, hostspans, manifest, reference, run, subscopes
from dynamo_tpu.models import mla
from dynamo_tpu.models.registry import get_model

#: DeepSeek-V2-Lite's config.json as published (the catalog's row)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400,
}
PEAKS = json.loads((manifest.HERE / "peaks.json").read_text())["TPU v5 lite"]


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def conf(man):
    return manifest.config_of(man, manifest.cell(man, "dsv2lite-docgen"))


def test_the_file_holds_every_published_number_but_the_two_it_lists(
        man, conf):
    entry = next(c for c in man["configs"]
                 if c["name"] == "deepseek-v2-lite-1chip")
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
        "config.json")
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    differ = sorted(k for k, v in PUBLISHED.items() if conf.get(k) != v)
    assert differ == sorted(conf["reduced"])
    assert conf["num_hidden_layers"] == 8  # layer 0 dense + 7 expert layers
    assert conf["max_position_embeddings"] == 8192
    assert "--max-context" in conf["serve_flags"]
    # one configuration file a configuration, and nothing else edited
    assert [c["name"] for c in man["configs"]] == [
        "qwen2-7b-int8", "phi3-mini-4k", "deepseek-v2-lite-1chip"]
    assert [w["name"] for w in man["workloads"]][-1] == "dsv2lite-docgen"


def test_every_published_width_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", reference)
    assert ref.__file__ == str(
        manifest.HERE / "references" / "deepseek_v2_lite.py")
    cfg = get_model(conf["preset"], dtype="bfloat16",
                    attention_impl="pallas").config
    widths = run.served_widths(cfg, ref)
    assert set(widths) >= {
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
        "moe_intermediate_size", "first_k_dense_replace",
        "num_hidden_layers", "hidden_size", "intermediate_size",
        "num_attention_heads", "vocab_size"}
    assert all(k in conf and widths[k] == conf[k] for k in widths)
    # the kernel path is what the preset resolves to, not a coerced "xla"
    assert cfg.attention_impl == "pallas" and cfg.dtype == jnp.bfloat16
    assert cfg.num_dense_layers == 1 and cfg.num_moe_layers == 7
    # YaRN as published, the softmax scale as the HF port's
    rs = conf["rope_scaling"]
    assert (cfg.rope_scaling_factor, cfg.rope_mscale, cfg.rope_mscale_all_dim,
            cfg.rope_original_max_position, cfg.rope_beta_fast,
            cfg.rope_beta_slow) == (
        rs["factor"], rs["mscale"], rs["mscale_all_dim"],
        rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"])
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5)
    # the 27-layer preset is the same block at the published depth
    full = get_model("deepseek-v2-lite").config
    assert full.num_layers == 27 and full.rope_scaling_factor == 40.0


def test_the_reference_rotates_as_the_program_does(conf):
    """YaRN's table, written twice (models/mla.py after HF's
    _compute_yarn_parameters; the reference after the paper): the same
    frequencies and the same factor on cos and sin."""
    ref = manifest.module_of(conf, "reference_module", reference)
    cfg = mla.MlaConfig.deepseek_v2_lite(8)
    inv, att = mla._yarn_inv_freq_and_factor(cfg, 64)
    want_inv, want_att = ref.rope_table(conf, 64)
    np.testing.assert_allclose(np.asarray(inv), want_inv, rtol=1e-6)
    assert att == pytest.approx(want_att) == pytest.approx(1.0)
    # interpolated by 40 at the low frequencies, untouched at the high
    plain = 1.0 / (10000.0 ** (np.arange(0, 64, 2) / 64))
    assert want_inv[0] == pytest.approx(plain[0])
    assert want_inv[-1] == pytest.approx(plain[-1] / 40)


def test_costs_on_hand_computed_bytes(conf):
    cost = manifest.module_of(conf, "costs_module", costs)
    assert cost.__file__ == str(manifest.HERE / "costs_deepseek_v2_lite.py")
    w = {"itemsize": 2}
    # a token: 8 layers x (512 + 64) x 2 B = 9216 B; cached 8 x 640 x 2
    assert cost.kv_bytes_per_token(conf) == 9216
    assert cost.cached_bytes_per_token(conf) == 10240
    assert cost.kv_read_bytes(conf, w, 250_000, 64) == 250_000 * 9216
    # one expert: 3 x 2048 x 1408 x 2 B = 17,301,504 B; 64 of them in
    # each of 7 layers = 7.75 GB when every expert is touched
    one = 3 * 2048 * 1408 * 2
    assert one == 17_301_504
    assert cost.experts_touched(conf, 64) == pytest.approx(63.88, abs=0.01)
    assert cost.moe_experts_read_bytes(conf, w, 0, 10_000) == pytest.approx(
        7 * 64 * one)
    assert cost.moe_experts_read_bytes(conf, w, 0, 1) == pytest.approx(
        7 * 6 * one)
    # everything else a step streams, by hand: attention 13.77 M a layer
    attn = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 2048 * 2048
            + 2 * 2048 + 512)
    shared_router = 3 * 2048 * 2816 + 2048 * 64
    rest = (8 * attn + 3 * 2048 * 10944 + 7 * shared_router + 2048
            + 2048 * 102400) * 2
    assert rest == pytest.approx(1.02e9, rel=0.01)
    assert cost.step_read_bytes(conf, w, 0, 10_000) == pytest.approx(
        rest + 7 * 64 * one)
    assert cost.step_read_bytes(conf, w, 1000, 10_000) - cost.step_read_bytes(
        conf, w, 0, 10_000) == 1000 * 9216
    # and against the program's own trees at the toy size
    tiny = mla.MlaConfig.tiny_moe()
    hf = conf["rehearsal"]["hf"]
    tree = mla.init_params(jax.random.key(0), tiny)
    whole = sum(x.nbytes for x in jax.tree.leaves(tree))
    assert cost.step_read_bytes(hf, {"itemsize": 4}, 0, 10_000) == \
        pytest.approx(whole - tree["embed"].nbytes)
    kv = mla.init_kv_pages(tiny, 8, 4)
    assert cost.kv_read_bytes(hf, {"itemsize": 4}, 32, 1) == \
        kv.k.nbytes + kv.v.nbytes
    padded = mla.init_kv_pages(
        dataclasses.replace(tiny, attention_impl="pallas"), 8, 4)
    assert cost.cached_bytes_per_token(hf, 4) * 32 == \
        padded.k.nbytes + padded.v.nbytes


# -- the new readers on a made-up trace ----------------------------------------


def make_trace(path, host=(), modules=(), ops=()):
    """As tests/chipbench/test_chipbench_hostspans.py's: host (name,
    start_us, dur_us, {arg: value}); modules (name, start_us, dur_us); ops
    (name, start_us, dur_us, tf_op)."""
    space = hostspans._xspace_class()()

    def add(plane, line, mid, name, start_us, dur_us):
        if not any(e.key == mid for e in plane.event_metadata):
            entry = plane.event_metadata.add(key=mid)
            entry.value.id, entry.value.name = mid, name
        return line.events.add(metadata_id=mid, offset_ps=start_us * 10**6,
                               duration_ps=dur_us * 10**6)

    def stat_id(plane, name):
        for e in plane.stat_metadata:
            if e.value.name == name:
                return e.key
        e = plane.stat_metadata.add(key=len(plane.stat_metadata) + 1)
        e.value.id, e.value.name = e.key, name
        return e.key

    plane = space.planes.add(id=1, name="/host:CPU")
    line = plane.lines.add(id=7, name="python3", timestamp_ns=1000)
    ids: dict = {}
    for name, start, dur, args in host:
        ev = add(plane, line, ids.setdefault(name, len(ids) + 1), name,
                 start, dur)
        for k, v in args.items():
            st = ev.stats.add(metadata_id=stat_id(plane, k))
            if isinstance(v, str):
                st.str_value = v
            else:
                st.int64_value = v
    plane = space.planes.add(id=2, name="/device:TPU:0")
    ml = plane.lines.add(id=1, name="XLA Modules", timestamp_ns=1000)
    ol = plane.lines.add(id=2, name="XLA Ops", timestamp_ns=1000)
    ids = {}
    for name, start, dur in modules:
        add(plane, ml, ids.setdefault(name, len(ids) + 1), name, start, dur)
    for name, start, dur, tf_op in ops:
        mid = ids.setdefault(name, len(ids) + 1)
        add(plane, ol, mid, name + " = f32[] op()", start, dur)
        md = next(e.value for e in plane.event_metadata if e.key == mid)
        if tf_op and not md.stats:
            md.stats.add(metadata_id=stat_id(plane, "tf_op"),
                         str_value=tf_op)
    path.write_bytes(space.SerializeToString())


BODY = "jit(multi_fn)/while/body/closed_call/"
#: one dispatch of two fused steps, 20 ms of device time
SPARSE_OPS = [
    ("%while.1", 0, 20000, "jit(multi_fn)/while:"),
    ("%fusion.2", 0, 1000, BODY + "attn/qkv/dot_general:"),
    ("%fusion.3", 1000, 400, BODY + "attn/absorb/dot_general:"),
    ("%kernel.4", 1400, 5000, BODY + "attn/paged/paged_decode_attention:"),
    ("%fusion.5", 6400, 600, BODY + "attn/out/dot_general:"),
    ("%fusion.6", 7000, 1200, BODY + "mlp/moe/route/sort:"),
    ("%ragged.7", 8200, 9000, BODY + "mlp/moe/experts/ragged_dot:"),
    ("%fusion.8", 17200, 800, BODY + "mlp/moe/route/gather:"),
    ("%fusion.9", 18000, 1000, BODY + "mlp/moe/shared/dot_general:"),
    ("%fusion.10", 19000, 1000, "jit(multi_fn)/while/body/lm_head/dot:"),
]


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)
    hostspans._THIS_RUN.clear()
    subscopes.load_deep.cache_clear()

    def place(ops):
        d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        make_trace(
            d / "host.xplane.pb",
            host=[("engine.launch", 0, 5, {"kind": "decode_multi", "k": 2})],
            modules=[("jit_multi_fn(1)", 10, 20000)],
            ops=[(n, s + 10, d_, p) for n, s, d_, p in ops])
        hostspans._THIS_RUN.clear()
        subscopes.load_deep.cache_clear()

    yield place
    hostspans._THIS_RUN.clear()
    subscopes.load_deep.cache_clear()


def reader_ctx(conf) -> dict:
    fused = {"kind": "decode_multi", "n_decode": 64, "tokens": 128}
    return {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1, "active_pages": 4000},
                   {"kind": "mixed", "ts": 100.2, "n_decode": 64,
                    "n_prefill": 1, "tokens": 65, "active_pages": 4000}],
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {},
        "costs": manifest.module_of(conf, "costs_module", costs),
    }


def test_deep_scopes_are_read_beside_the_ones_hostspans_names():
    assert subscopes.deep_scope_of(BODY + "mlp/moe/experts/x:") == \
        "mlp/moe/experts"
    assert subscopes.deep_scope_of(BODY + "mlp/moe/route/sort:") == \
        "mlp/moe/route"
    assert subscopes.deep_scope_of(BODY + "attn/absorb/dot:") == "attn/absorb"
    # what hostspans names stays as it names it
    for path in (BODY + "attn/paged/k:", BODY + "mlp/dot:", "jit(f)/x:",
                 BODY + "attn/qkv/mlp/moe/experts/x:"):
        assert subscopes.deep_scope_of(path) == hostspans.scope_of(path)
    assert hostspans.scope_of(BODY + "mlp/moe/experts/x:") == "mlp"


def test_new_readers_on_a_sparse_latent_decoders_trace(conf, run_dir, capsys):
    run_dir(SPARSE_OPS)
    ctx = reader_ctx(conf)
    read = manifest.layer_reader
    # two fused steps: route (1200 + 800) / 2 us, absorb 400 / 2 us
    assert read("moe_route_ms_per_step")(ctx) == pytest.approx(1.0)
    assert read("attn_absorb_ms_per_step")(ctx) == pytest.approx(0.2)
    # experts: 9 ms over 2 steps; 63.88 experts x 7 layers x 17.3 MB
    nbytes = 7 * 63.8808 * 17_301_504
    assert read("moe_experts_hbm_share")(ctx) == pytest.approx(
        100.0 * nbytes / 4.5e-3 / 819e9, rel=1e-4)
    # the readers the benchmark had read the same trace through the
    # scopes hostspans names: the whole FFN under `mlp`, absorb in `attn`
    assert read("decode_mlp_ms_per_step")(ctx) == pytest.approx(6.0)
    assert read("decode_attn_ms_per_step")(ctx) == pytest.approx(3.5)
    live = 4000 * 64 - 64 * 32
    assert read("paged_attn_hbm_share")(ctx) == pytest.approx(
        100.0 * live * 9216 / 2.5e-3 / 819e9, rel=1e-4)


def test_new_readers_give_none_on_a_dense_decoders_trace(conf, run_dir,
                                                         capsys):
    """The parent commit's programs, or a dense decoder's: no such scope
    in the trace, nothing to read, no error."""
    run_dir([(n, s, d, p.replace("/moe/experts", "").replace(
        "/moe/route", "").replace("/moe/shared", "").replace(
            "attn/absorb", "attn/qkv")) for n, s, d, p in SPARSE_OPS])
    ctx = reader_ctx(conf)
    for name in ("moe_experts_hbm_share", "moe_route_ms_per_step",
                 "attn_absorb_ms_per_step"):
        assert manifest.layer_reader(name)(ctx) is None
    assert manifest.layer_reader("decode_mlp_ms_per_step")(ctx) == \
        pytest.approx(6.0)
    # no trace at all (an untraced run, a CPU rehearsal)
    (manifest.RUN_DIR / "trace" / "cell" / "plugins" / "profile" / "t"
     / "host.xplane.pb").unlink()
    hostspans._THIS_RUN.clear()
    assert manifest.layer_reader("moe_route_ms_per_step")(ctx) is None
    # a dense configuration's cost module has no expert bytes to give
    run_dir(SPARSE_OPS)
    assert manifest.layer_reader("moe_experts_hbm_share")(
        {**ctx, "costs": costs}) is None


def test_the_new_metrics_name_the_cell_and_the_cell_reports_the_old_ones(man):
    new = {"moe_experts_hbm_share", "moe_route_ms_per_step",
           "attn_absorb_ms_per_step"}
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in new:
        assert per_layer[name]["workloads"] == ["dsv2lite-docgen"]
        assert per_layer[name]["moves"] == "output_tok_s"
    wanted = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", "dsv2lite-docgen")}
    assert new <= wanted and "paged_attn_hbm_share" in wanted
    assert "itl_p95_ms.longgen" not in wanted
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", "dsv2lite-docgen")} == {"output_tok_s", "setup_s"}
    assert new.isdisjoint(m["name"] for m in manifest.metrics_of(
        man, "per_layer", "qwen2-longgen"))


def test_routing_probe_counts_what_a_decode_steps_rows_touch(conf, run_dir,
                                                             monkeypatch):
    from chipbench.references import deepseek_v2_lite as ref

    hf = conf["rehearsal"]["hf"]
    params = mla.init_params(jax.random.key(0), mla.MlaConfig.tiny_moe())
    probe = ref.routing_probe(params, hf, rows=64, tokens=8)
    assert probe["rows"] == 64 and len(probe["per_layer_touched"]) == 2
    assert 1 <= probe["experts_touched"] <= 4
    assert probe["expert_load_max_over_mean"] >= 1.0
    # `compare` leaves it where the reader of moe_experts_hbm_share looks:
    # fewer distinct experts than even routing expects, fewer bytes
    run_dir(SPARSE_OPS)
    ctx = reader_ctx(conf)
    read = manifest.layer_reader("moe_experts_hbm_share")
    even = read(ctx)
    (manifest.RUN_DIR / ref.PROBE_FILE).write_text(json.dumps(
        {"pid": os.getpid(), "rows": 64, "experts_touched": 48.0}))
    assert read(ctx) == pytest.approx(even * 48.0 / 63.8808, rel=1e-4)
    # another process's file, or another batch size, is not this run's
    (manifest.RUN_DIR / ref.PROBE_FILE).write_text(json.dumps(
        {"pid": os.getpid() + 1, "rows": 64, "experts_touched": 48.0}))
    assert read(ctx) == even
    (manifest.RUN_DIR / ref.PROBE_FILE).write_text(json.dumps(
        {"pid": os.getpid(), "rows": 16, "experts_touched": 48.0}))
    assert read(ctx) == even


def test_the_control_lowers_to_what_int8_can_hold():
    from chipbench.references import deepseek_v2_lite as ref

    tree = mla.init_params(jax.random.key(0), mla.MlaConfig.tiny_moe())
    lp = jax.tree.map(lambda a: a[0], tree["moe_layers"])
    low = ref.to_int8(lp)
    for name in ("wq", "we_gate", "ws_down"):
        w, q = np.asarray(lp[name]), np.asarray(low[name])
        scale = np.abs(w).max(axis=-2, keepdims=True) / 127.0
        np.testing.assert_allclose(q / scale, np.round(q / scale), atol=1e-3)
        assert 0 < np.abs(q - w).max() <= scale.max() / 2 + 1e-7
    assert np.array_equal(np.asarray(low["w_router"]),
                          np.asarray(lp["w_router"]))
    assert set(ref.MUST_FAIL) == {"int8_weights", "dropped_expert"}
    assert set(ref.CONTROLS) == set(ref.MUST_FAIL) | {"bf16_router"}


def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset mla-tiny-moe, float32, the kernels interpreted: chunked
    prefill over latent history, the fused decode walk, the staged write
    and the dropless dispatch through run in=http, and the reference
    agrees. Never a result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(manifest.ROOT)}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "dsv2lite-docgen", "--seed", "3000000019", "--seconds", "5",
         "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert last["failed"] == 0
    assert set(last["metrics"]) == {"output_tok_s", "setup_s"}
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert notes["serve_up"]["model"] == "mla-tiny-moe"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    assert notes["correct"]["widths_as_published"] is True
    assert notes["reference"]["passed"] is True
    assert notes["reference"]["tokens"] == 128
    assert notes["reference"]["max_logprob_drift"] < 1e-3
    assert notes["reference"]["expert_load_max_over_mean"] >= 1.0
    assert 1 <= notes["reference"]["experts_touched_at_64_rows"] <= 4
    kinds = {p["key"].split(",")[0].strip("('") for p in
             notes["programs"]["seen"]}
    assert {"mixed", "decode_multi"} <= kinds
    assert notes["window"]["preemptions"] == 0
