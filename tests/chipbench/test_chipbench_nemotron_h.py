"""`nemotron3-nano-30b-a3b-1chip` and `nano3-chat-churn` through the seam
PR 26 built: the configuration file against the published numbers, the
served widths, the cost module on hand-computed bytes and operations,
each new per-layer reader on a made-up trace (None on a trace without
its scopes), the control, the plan's program family, and the cell's CPU
rehearsal."""
import collections
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (costs, hostspans, manifest, reference, run, ssmscopes,
                       subscopes, traffic)
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.registry import get_model
from test_chipbench_deepseek_v2_lite import PEAKS, make_trace

#: NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's config.json as published (the
#: catalog's row, every key)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
NEW = ("ssm_ms_per_step", "ssm_scan_hbm_share", "ssm_chunk_flops_share",
       "moe_experts_hbm_share.nano3", "state_slots_live_share")


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def conf(man):
    return manifest.config_of(man, manifest.cell(man, "nano3-chat-churn"))


@pytest.fixture(scope="module")
def cost(conf):
    return manifest.module_of(conf, "costs_module", costs)


def test_the_file_holds_every_published_number_but_the_three_it_lists(
        man, conf):
    entry = next(c for c in man["configs"]
                 if c["name"] == "nemotron3-nano-30b-a3b-1chip")
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "max_position_embeddings"]
    differ = sorted(k for k, v in PUBLISHED.items() if conf.get(k) != v)
    assert differ == sorted(conf["reduced"])
    assert conf["num_hidden_layers"] == 28 and conf["n_routed_experts"] == 16
    assert conf["max_position_embeddings"] == 4096
    assert (conf["n_routed_experts_published"],
            conf["num_hidden_layers_published"]) == (128, 52)
    assert "8 chips" in conf["deployment"]
    for key in (*conf["reduced"], "rope", "ssm_state_dtype", "weights",
                "num_pages", "max_seqs", "expert_width_stored"):
        assert key in conf["assumed"], key
    tol = conf["reference_tolerance"]
    assert set(tol) >= {"min_argmax_agreement", "max_logprob_drift",
                        "max_mean_logprob_drift", "max_ssm_state_distance",
                        "why"}
    # one configuration and one cell, appended
    assert man["configs"][-1] is entry
    cell = man["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "nano3-chat-churn", "nemotron3-nano-30b-a3b-1chip", "chat-churn", 1)


def test_every_published_width_is_served_by_the_preset(conf):
    ref = manifest.module_of(conf, "reference_module", reference)
    assert ref.__file__ == str(manifest.HERE / "references" / "nemotron_h.py")
    cfg = get_model(conf["preset"], dtype="bfloat16",
                    attention_impl="pallas").config
    widths = run.served_widths(cfg, ref)
    assert set(widths) >= {
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "vocab_size", "mamba_num_heads",
        "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
        "chunk_size", "n_routed_experts", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok"}
    assert all(k in conf and widths[k] == conf[k] for k in widths)
    assert cfg.attention_impl == "pallas" and cfg.dtype == jnp.bfloat16
    assert cfg.pattern == ref.pattern_of(conf) == "MEMEM*E" * 4
    assert cfg.experts_held == (conf["experts_held_first"], 16)
    assert cfg.expert_width == conf["expert_width_stored"] == 1920
    assert cfg.attn_cfg.use_rope is False
    # the rehearsal preset under the rehearsal's own keys
    tiny = get_model(conf["rehearsal"]["preset"]).config
    hf = conf["rehearsal"]["hf"]
    small = run.served_widths(tiny, ref)
    assert all(k in hf and small[k] == hf[k] for k in small)
    assert tiny.pattern == ref.pattern_of(hf)


def test_costs_on_hand_computed_bytes_and_operations(conf, cost):
    assert cost.__file__ == str(manifest.HERE / "costs_nemotron_h.py")
    w = {"itemsize": 2}
    assert (cost.layers(conf, "M"), cost.layers(conf, "E"),
            cost.layers(conf, "*")) == (12, 12, 4)
    # a row's state: 12 layers x (64 x 64 x 128 x 4 B + 3 x 6144 x 2 B)
    one = 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert one == 2_134_016
    assert cost.ssm_state_bytes_per_row(conf) == 12 * one
    assert cost.ssm_state_bytes(conf, w, 0, 63) == 2 * 63 * 12 * one
    assert cost.ssm_state_bytes(conf, w, 0, 63) == pytest.approx(3.23e9,
                                                                 rel=0.01)
    # a token's K and V: 4 layers x 2 heads x 128 x 2 x 2 B
    assert cost.kv_bytes_per_token(conf) == 4096
    assert cost.kv_read_bytes(conf, w, 100_000, 64) == 100_000 * 4096
    # an expert as stored: 2 x 2688 x 1920 x 2 B; 15.26 of 16 touched
    expert = 2 * 2688 * 1920 * 2
    assert expert == 20_643_840
    assert cost.experts_touched(conf, 64) == pytest.approx(15.26, abs=0.01)
    assert cost.moe_experts_read_bytes(conf, w, 0, 10_000) == pytest.approx(
        12 * 16 * expert)
    assert cost.moe_experts_read_bytes(conf, w, 0, 64, touched=14.0) == \
        pytest.approx(12 * 14 * expert)
    # everything else a step streams, against the program's own tree
    cfg = nh.NemotronHConfig.nemotron3_nano_1chip()
    tree = jax.eval_shape(lambda: nh.init_params(jax.random.key(0), cfg))
    nbytes = lambda t: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(t))
    experts = nbytes({k: tree["moe"][k] for k in ("we_up", "we_down")})
    assert experts == 12 * 16 * expert
    dense = nbytes(tree) - experts - nbytes(tree["embed"])
    assert cost.dense_weight_bytes(conf) == dense
    assert nbytes(tree) == pytest.approx(6.99e9, rel=0.005)
    assert cost.step_read_bytes(conf, w, 0, 10_000) == pytest.approx(
        dense + experts + 2 * 10_000 * 12 * one)
    assert cost.step_read_bytes(conf, w, 1000, 64) - cost.step_read_bytes(
        conf, w, 0, 64) == 1000 * 4096
    # the issue's floor at 63 rows and ~100k live tokens: ~9.5 GB
    assert cost.step_read_bytes(conf, w, 100_000, 63) == pytest.approx(
        9.6e9, rel=0.03)
    # a prompt chunk's conv and scan, by hand for one token of a 512 chunk
    per_token = (2 * 8 * 128 * 128 / 2 + 2 * 64 * 64 * 128 / 2
                 + 4 * 64 * 64 * 128 + 2 * 4 * 6144)
    assert cost.ssm_chunk_flops(conf, 512) == 12 * 512 * per_token
    assert cost.ssm_chunk_flops(conf, 512) == pytest.approx(1.7e10, rel=0.02)
    # and at the toy size against the program's trees and pools
    tiny = nh.NemotronHConfig.tiny()
    hf = conf["rehearsal"]["hf"]
    small = nh.init_params(jax.random.key(0), tiny)
    assert cost.step_read_bytes(hf, {"itemsize": 4}, 0, 10_000) == \
        nbytes(small) - small["embed"].nbytes + 2 * 10_000 * (
            nh.state_bytes_per_slot(tiny))
    cache = nh.init_cache(tiny, 8, 4, 3)
    assert cost.ssm_state_bytes_per_row(hf, 4) * 8 == \
        cache.conv.nbytes + cache.ssm.nbytes
    assert cost.kv_read_bytes(hf, {"itemsize": 4}, 32, 1) == \
        cache.k.nbytes + cache.v.nbytes


# -- the new readers on a made-up trace ----------------------------------------

BODY = "jit(multi_fn)/while/body/while/body/closed_call/"
MIXED = "jit(mixed_fn)/while/body/closed_call/"
#: one fused dispatch of two steps (30 ms), one mixed step (10 ms)
HYBRID_OPS = [
    ("%while.1", 0, 30000, "jit(multi_fn)/while:"),
    ("%fusion.2", 0, 1500, BODY + "attn/ssm/in_proj/dot_general:"),
    ("%fusion.3", 1500, 500, BODY + "attn/ssm/conv/add:"),
    ("%kernel.4", 2000, 500, BODY + "attn/ssm/conv/state_write_rows:"),
    ("%kernel.5", 2500, 7000, BODY + "attn/ssm/scan/ssm_decode_step:"),
    ("%fusion.6", 9500, 300, BODY + "attn/ssm/gate_norm/mul:"),
    ("%fusion.7", 9800, 700, BODY + "attn/ssm/out/dot_general:"),
    ("%kernel.8", 10500, 1500, BODY + "attn/paged/paged_decode_attention:"),
    ("%fusion.9", 12000, 1000, BODY + "mlp/moe/route/sort:"),
    ("%gmm.10", 13000, 12000, BODY + "mlp/moe/experts/gmm:"),
    ("%fusion.11", 25000, 1000, BODY + "mlp/moe/shared/dot_general:"),
    ("%fusion.12", 26000, 1000, "jit(multi_fn)/while/body/lm_head/dot:"),
    ("%fusion.20", 40000, 1000, MIXED + "attn/ssm/in_proj/dot_general:"),
    ("%fusion.21", 41000, 1000, MIXED + "attn/ssm/conv/add:"),
    ("%fusion.22", 42000, 4000, MIXED + "attn/ssm/scan/dot_general:"),
    ("%gmm.23", 46000, 4000, MIXED + "mlp/moe/experts/gmm:"),
]


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)

    def clear():
        hostspans._THIS_RUN.clear()
        subscopes.load_deep.cache_clear()
        ssmscopes.load_deep.cache_clear()

    def place(ops):
        d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        make_trace(
            d / "host.xplane.pb",
            host=[("engine.launch", 0, 5, {"kind": "decode_multi", "k": 2}),
                  ("engine.launch", 20, 5, {"kind": "mixed", "k": 1})],
            modules=[("jit_multi_fn(1)", 10, 30000),
                     ("jit_mixed_fn(2)", 40010, 10000)],
            ops=[(n, s + 10, d_, p) for n, s, d_, p in ops])
        clear()

    clear()
    yield place
    clear()


def reader_ctx(conf) -> dict:
    fused = {"kind": "decode_multi", "n_decode": 63, "tokens": 126}
    return {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [{**fused, "ts": 100.1, "active_pages": 2000},
                   {"kind": "mixed", "ts": 100.2, "n_decode": 63,
                    "n_prefill": 1, "prefill_tokens": 300, "tokens": 64,
                    "active_pages": 2000}],
        "hf": conf, "weights": conf["weights"], "page_size": 64,
        "kernels": True, "peaks": PEAKS, "engine": {},
        "engine_now": {"state_slots": 72, "state_slots_live": 66},
        "costs": manifest.module_of(conf, "costs_module", costs),
    }


def test_deep_scopes_are_read_beside_the_ones_hostspans_names():
    assert ssmscopes.deep_scope_of(BODY + "attn/ssm/scan/x:") == \
        "attn/ssm/scan"
    assert ssmscopes.deep_scope_of(MIXED + "attn/ssm/conv/add:") == \
        "attn/ssm/conv"
    # the experts' scope is subscopes.py's, as in dsv2lite-docgen
    assert subscopes.deep_scope_of(BODY + "mlp/moe/experts/gmm:") == \
        "mlp/moe/experts"
    # hostspans folds the mixer into `attn`, and subscopes does not name it
    assert hostspans.scope_of(BODY + "attn/ssm/scan/x:") == "attn"
    assert subscopes.deep_scope_of(BODY + "attn/ssm/scan/x:") == "attn"
    for path in (BODY + "attn/paged/k:", BODY + "mlp/dot:", "jit(f)/x:",
                 BODY + "attn/qkv/attn/ssm/scan/x:"):
        assert ssmscopes.deep_scope_of(path) == hostspans.scope_of(path)


def test_new_readers_on_a_hybrid_decoders_trace(conf, cost, run_dir, capsys):
    run_dir(HYBRID_OPS)
    ctx = reader_ctx(conf)
    read = manifest.layer_reader
    # two fused steps: all of attn/ssm is 10.5 ms, conv + scan 8 ms
    assert read("ssm_ms_per_step")(ctx) == pytest.approx(5.25)
    state = 2 * 63 * 12 * 2_134_016
    assert read("ssm_scan_hbm_share")(ctx) == pytest.approx(
        100.0 * state / 4e-3 / 819e9, rel=1e-6)
    # experts: 12 ms over 2 steps; 15.23 of 16 held x 12 layers x 20.6 MB
    nbytes = 12 * cost.experts_touched(conf, 63) * 20_643_840
    assert read("moe_experts_hbm_share.nano3")(ctx) == pytest.approx(
        100.0 * nbytes / 6e-3 / 819e9, rel=1e-6)
    # the mixed step: 300 prompt tokens' scan and conv in 5 ms
    flops = cost.ssm_chunk_flops(conf, 300)
    assert read("ssm_chunk_flops_share")(ctx) == pytest.approx(
        100.0 * flops / 5e-3 / 197e12, rel=1e-6)
    assert read("state_slots_live_share")(ctx) == pytest.approx(
        100.0 * 66 / 72)
    for name in NEW[1:4]:
        assert 0 < read(name)(ctx) <= 100
    # the readers the benchmark had read the same trace through the
    # scopes hostspans names: the mixer inside `attn`
    assert read("decode_attn_ms_per_step")(ctx) == pytest.approx(6.0)
    assert read("decode_mlp_ms_per_step")(ctx) == pytest.approx(7.0)
    assert read("moe_route_ms_per_step")(ctx) == pytest.approx(0.5)
    live = 2000 * 64 - 63 * 32
    assert read("paged_attn_hbm_share")(ctx) == pytest.approx(
        100.0 * live * 4096 / 0.75e-3 / 819e9, rel=1e-4)
    # the probe's count of held experts, where this process left one
    (manifest.RUN_DIR / "nemotron_h_routing_probe.json").write_text(
        json.dumps({"pid": os.getpid(), "rows": 64, "experts_touched": 14.0}))
    assert read("moe_experts_hbm_share.nano3")(ctx) == pytest.approx(
        100.0 * 12 * 14.0 * 20_643_840 / 6e-3 / 819e9, rel=1e-6)


def test_new_readers_give_none_on_a_trace_without_the_scopes(conf, run_dir,
                                                             capsys):
    """The parent commit's programs, or another configuration's: no such
    scope in the trace, no such counter, nothing to read, no error."""
    run_dir([(n, s, d, p.replace("attn/ssm/scan", "attn/paged").replace(
        "attn/ssm/conv", "attn/kv_update").replace(
            "attn/ssm/", "attn/").replace("/moe/experts", ""))
        for n, s, d, p in HYBRID_OPS])
    ctx = {**reader_ctx(conf), "engine_now": {"kv_total_pages": 100}}
    for name in NEW:
        assert manifest.layer_reader(name)(ctx) is None, name
    assert manifest.layer_reader("decode_attn_ms_per_step")(ctx) == \
        pytest.approx(6.0)
    # no trace at all (an untraced run, a CPU rehearsal)
    (manifest.RUN_DIR / "trace" / "cell" / "plugins" / "profile" / "t"
     / "host.xplane.pb").unlink()
    hostspans._THIS_RUN.clear()
    for name in NEW[:4]:
        assert manifest.layer_reader(name)(ctx) is None, name
    # a dense configuration's cost module has no state bytes to give
    run_dir(HYBRID_OPS)
    for name in ("ssm_scan_hbm_share", "ssm_chunk_flops_share",
                 "moe_experts_hbm_share.nano3"):
        assert manifest.layer_reader(name)({**ctx, "costs": costs}) is None


def test_the_new_metrics_name_the_cell_and_the_cell_reports_the_old_ones(man):
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == ["nano3-chat-churn"]
        assert per_layer[name]["moves"] == "output_tok_s"
    assert [m["name"] for m in man["per_layer"]][-5:] == list(NEW)
    wanted = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", "nano3-chat-churn")}
    assert set(NEW) <= wanted
    assert {"paged_attn_hbm_share", "decode_hbm_share", "hbm_live_share",
            "pipelined_launch_share", "mixed_step_device_ms"} <= wanted
    assert wanted.isdisjoint({"moe_experts_hbm_share", "itl_p95_ms.longgen"})
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", "nano3-chat-churn")} == {"output_tok_s", "setup_s"}
    for cell in ("qwen2-longgen", "phi3-chat-closed", "dsv2lite-docgen"):
        assert set(NEW).isdisjoint(m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell))


def _bucket(n: int) -> int:
    t = 32
    while t < n:
        t *= 2
    return min(t, 512)


def _pieces(prompt: int):
    """(T bucket, first chunk, sampled) of each piece of a prompt."""
    n = -(-prompt // 512)
    return [(_bucket(512 if i < n - 1 else prompt - 512 * (n - 1)), i == 0,
             i == n - 1) for i in range(n)]


def test_the_ramp_meets_every_member_of_the_mixed_family(man):
    """The plan is the same in every run (`shape_seed`), so which prompt
    shapes fall before the window is a property of the file: a coarse
    simulation of the closed loop (64 slots, one prompt piece a step) has
    met every (T bucket, first chunk, sampled) member the traffic ever
    meets long before `ramp_tokens` are delivered, and meets no new one in
    the six windows' worth of tokens after it."""
    mix = manifest.traffic_of(manifest.cell(man, "nano3-chat-churn"))
    assert (mix["clients"], mix["prompt_tokens"]["median"],
            mix["output_tokens"]["median"]) == (80, 512, 192)
    assert mix["first_prompt_tokens"] == {
        "dist": "const", "value": 32, "why": mix["first_prompt_tokens"]["why"]}
    plan = traffic.plan(mix, 1, 1000)
    nxt, queue = [0] * 80, collections.deque(range(80))
    running, delivered, first_seen = [], 0, {}
    while delivered < 600_000 and (queue or running):
        while queue and len(running) < 64:
            c = queue.popleft()
            turn = plan.clients[c][nxt[c]]
            running.append([c, _pieces(len(turn.new_ids)), turn.max_tokens])
        for r in running:
            if r[1]:
                member = r[1].pop(0)
                if nxt[r[0]] > 0:  # the first prompts are the prefill burst
                    first_seen.setdefault(member, delivered)
                break
        for r in list(running):
            if not r[1]:
                r[2] -= 1
                delivered += 1
                if r[2] <= 0:
                    running.remove(r)
                    nxt[r[0]] += 1
                    if nxt[r[0]] < len(plan.clients[r[0]]):
                        queue.append(r[0])
    assert len(first_seen) == 11  # 2 whole chunks, 5 tails, 4 whole prompts
    assert max(first_seen.values()) < mix["ramp_tokens"] / 3
    assert delivered >= 600_000  # the clients outlast ramp and window


def test_the_control_lowers_each_of_its_four_ways(conf):
    ref = manifest.module_of(conf, "reference_module", reference)
    assert set(ref.CONTROLS) == {
        "bf16_state", "int8_weights", "dropped_expert", "no_skip_term"}
    tiny = nh.NemotronHConfig.tiny()
    hf = conf["rehearsal"]["hf"]
    params = nh.init_params(jax.random.key(0), tiny)
    lp = jax.tree.map(lambda a: a[0], params["mamba"])
    low = ref.to_int8(lp)
    for name in ("in_proj", "out_proj"):
        w, q = np.asarray(lp[name]), np.asarray(low[name])
        scale = np.abs(w).max(axis=-2, keepdims=True) / 127.0
        np.testing.assert_allclose(q / scale, np.round(q / scale), atol=1e-3)
        assert 0 < np.abs(q - w).max() <= scale.max() / 2 + 1e-7
    assert np.array_equal(np.asarray(low["A_log"]), np.asarray(lp["A_log"]))
    ids = np.random.default_rng(0).integers(10, 256, 40)
    base = ref.log_probs(params, hf, ids, [39])[0]
    moved = {
        "bf16_state": ref.log_probs(params, hf, ids, [39],
                                    state_dtype=jnp.bfloat16)[0],
        "int8_weights": ref.log_probs(params, hf, ids, [39],
                                      lower=ref.to_int8)[0],
        "no_skip_term": ref.log_probs(params, hf, ids, [39], skip=False)[0],
        "dropped_expert": ref.log_probs(
            params, {**hf, "num_experts_per_tok": 1}, ids, [39])[0],
    }
    for name, lp_ in moved.items():
        assert np.abs(lp_ - base).max() > 1e-4, name
    # the skip term and a whole expert are not roundings
    assert np.abs(moved["no_skip_term"] - base).max() > 0.05
    assert np.abs(moved["dropped_expert"] - base).max() > 0.05


@pytest.mark.parametrize(
    "case", ["program", "program_kernel", "bf16_pool", "bf16_control"])
def test_state_distance_reads_the_precision_the_state_is_carried_in(
        conf, case, monkeypatch):
    """`compare` holds the state to float32 (`max_ssm_state_distance`):
    the program's pool and decode routine, jnp and kernel, read a rounding
    of float32; a pool that holds bfloat16 (what halving the state's bytes
    would do) and the control's bfloat16 recurrence read a rounding of
    bfloat16 and fail by that limit alone, through the one key of the
    harness's verdict that carries it."""
    import functools

    from dynamo_tpu.ops import ssm_state

    ref = manifest.module_of(conf, "reference_module", reference)
    monkeypatch.setattr(ref, "PROBE", False)
    tol = conf["reference_tolerance"]
    hf = {**conf["rehearsal"]["hf"], "reference_tolerance": tol}
    params = nh.init_params(jax.random.key(0), nh.NemotronHConfig.tiny())
    how = ({"state_dtype": jnp.dtype("bfloat16")}
           if case == "bf16_control" else {})
    streams = ref.control_streams(params, hf, 7, how, prompt_len=12,
                                  out_len=24, streams=1)
    assert len(streams[0]["ssm_state"]) == 3  # one a Mamba-2 layer
    if case != "bf16_control":
        del streams[0]["ssm_state"]  # the program's own routines
    if case == "program_kernel":
        monkeypatch.setattr(ssm_state, "ssm_decode_step", functools.partial(
            ssm_state.ssm_decode_step, use_kernel=True, interpret=True))
    if case == "bf16_pool":
        init = nh.init_cache
        monkeypatch.setattr(nh, "init_cache", lambda *a: (
            lambda c: c._replace(ssm=c.ssm.astype(jnp.bfloat16)))(init(*a)))
    res = ref.compare(params, hf, streams)
    if case.startswith("program"):
        assert res["ssm_state_distance"] < 1e-6
        assert res["mean_logprob_drift"] < 1e-5
        return
    assert res["ssm_state_distance"] > 10 * tol["max_ssm_state_distance"]
    assert res["mean_logprob_drift"] == float("inf")
    # nothing else tells it: the tokens' own drift is far inside its limit
    assert (res["mean_logprob_drift_of_tokens"]
            < tol["max_mean_logprob_drift"] / 10)
    assert res["argmax_agreement"] >= tol["min_argmax_agreement"]
    # without the served preset's name there is no pool to ask
    assert "ssm_state_distance" not in ref.compare(
        params, {k: v for k, v in hf.items() if k != "preset"}, streams)


def test_routing_probe_counts_the_held_experts_a_steps_rows_touch(conf):
    ref = manifest.module_of(conf, "reference_module", reference)
    tiny = nh.NemotronHConfig.tiny()
    params = nh.init_params(jax.random.key(0), tiny)
    probe = ref.routing_probe(params, conf["rehearsal"]["hf"], rows=64,
                              tokens=8)
    assert probe["rows"] == 64 and len(probe["per_layer_touched"]) == 3
    assert 1 <= probe["experts_touched"] <= 4  # of the 4 held
    assert probe["experts_touched"] <= probe["experts_touched_of_all"] <= 8
    assert 0.2 < probe["assignments_held_share"] < 0.8  # half are held


def test_rehearsal_of_the_cell_walks_the_whole_flow():
    """preset nemotron-h-tiny, float32, the kernels interpreted: chunked
    prefill from a state slot, the fused decode dispatch through the
    state kernel, mixed steps, launch-ahead, through run in=http, and the
    reference agrees. Never a result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(manifest.ROOT)}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "nano3-chat-churn", "--seed", "3000000019", "--seconds", "5",
         "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert last["failed"] == 0
    assert set(last["metrics"]) == {"output_tok_s", "setup_s"}
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert notes["serve_up"]["model"] == "nemotron-h-tiny"
    assert notes["serve_up"]["attention_impl"] == "pallas"
    assert notes["serve_up"]["memory"]["state_pool_bytes"] > 0
    assert notes["correct"]["widths_as_published"] is True
    assert notes["reference"]["passed"] is True
    assert notes["reference"]["tokens"] == 128
    assert notes["reference"]["max_logprob_drift"] < 1e-3
    assert 1 <= notes["reference"]["experts_held_touched_at_64_rows"] <= 4
    kinds = {p["key"].split(",")[0].strip("('") for p in
             notes["programs"]["seen"]}
    assert {"mixed", "decode_multi"} <= kinds
    assert notes["window"]["preemptions"] == 0
