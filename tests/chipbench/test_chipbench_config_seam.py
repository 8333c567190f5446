"""A configuration brings its own reference, widths and byte counts as
files (`reference_module`, `costs_module` in its file): an MLA + MoE
decoder — preset `mla-tiny-moe`, the files in tests/chipbench/mla_tiny_moe/
— goes through the harness with no file under chipbench/ edited. The
next `model_config` PR stands on this test. And the dense default reads
what it read before the seam, to the last digit."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, flight, hostspans, manifest, reference, run, trace
from dynamo_tpu.models import mla

MLA_DIR = manifest.ROOT / "tests" / "chipbench" / "mla_tiny_moe"
TESTDATA = manifest.HERE / "testdata"
PEAKS = json.loads((manifest.HERE / "peaks.json").read_text())["TPU v5 lite"]
#: the sizes of the configuration file, as the reference reads them
HF_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
           "num_attention_heads", "vocab_size", "q_lora_rank", "kv_lora_rank",
           "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
           "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
           "moe_intermediate_size", "first_k_dense_replace",
           "routed_scaling_factor", "norm_topk_prob", "rope_theta",
           "rms_norm_eps", "tie_word_embeddings")


def mla_conf() -> dict:
    conf = json.loads((MLA_DIR / "config.json").read_text())
    conf["rehearsal"] = {
        "preset": conf["preset"], "serve_flags": conf["serve_flags"],
        "hf": {k: conf[k] for k in HF_KEYS}, "weights": conf["weights"]}
    return conf


def grown_manifest(tmp_path, conf: dict) -> str:
    """BENCHMARK.json plus one configuration and one cell, in tmp_path."""
    (tmp_path / "configs").mkdir()
    file = tmp_path / "configs" / "mla-tiny-moe.json"
    file.write_text(json.dumps(conf))
    man = manifest.load()
    man["configs"].append({"name": "mla-tiny-moe", "source": conf["source"],
                           "file": str(file), "reduced": [], "why": "x"})
    man["workloads"].append({"name": "mla-tiny-moe-longgen", "chips": 1,
                             "config": "mla-tiny-moe", "traffic": "longgen",
                             "why": "x"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(man))
    return str(path)


def served_log_probs(params, cfg, ids):
    t, page = len(ids), 4
    kv = mla.init_kv_pages(cfg, num_pages=2 + t // page, page_size=page)
    pt = jnp.arange(1, 2 + t // page, dtype=jnp.int32)[None]
    logits, _kv = mla.forward(
        params, cfg, jnp.asarray([ids], jnp.int32),
        jnp.arange(t, dtype=jnp.int32)[None], jnp.ones((1, t), bool), kv, pt)
    return np.asarray(jax.nn.log_softmax(logits[0].astype(jnp.float32)))


def test_modules_are_found_by_file_and_default_to_the_dense_ones():
    conf = mla_conf()
    ref = manifest.module_of(conf, "reference_module", reference)
    cost = manifest.module_of(conf, "costs_module", costs)
    assert ref.__file__ == str(MLA_DIR / "reference.py")
    assert cost.__file__ == str(MLA_DIR / "costs.py")
    with open(manifest.HERE / "configs" / "qwen2-7b-int8.json") as f:
        qwen = json.load(f)
    assert manifest.module_of(qwen, "reference_module", reference) is reference
    assert manifest.module_of(qwen, "costs_module", costs) is costs


def test_the_mla_moe_reference_agrees_with_the_served_model():
    conf, cfg = mla_conf(), mla.MlaConfig.tiny_moe()
    ref = manifest.module_of(conf, "reference_module", reference)
    params = mla.init_params(jax.random.key(3), cfg)
    ids = [int(x) for x in np.random.default_rng(0).integers(1, 256, 24)]
    got = ref.log_probs(params, conf, ids, np.arange(len(ids)))
    want = served_log_probs(params, cfg, ids)
    assert np.abs(got - want).max() < 2e-4
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # and it is tight enough to see a piece go missing: no routed experts
    no_experts = {**conf, "routed_scaling_factor": 0.0}
    assert np.abs(ref.log_probs(params, no_experts, ids, np.arange(len(ids)))
                  - want).max() > 0.01


def test_served_widths_come_from_the_module_and_every_key_is_compared():
    conf, cfg = mla_conf(), mla.MlaConfig.tiny_moe()
    ref = manifest.module_of(conf, "reference_module", reference)
    widths = run.served_widths(cfg, ref)
    assert widths["kv_lora_rank"] == 32 and widths["n_routed_experts"] == 4
    assert "num_key_value_heads" not in widths  # MlaConfig has no head_dim
    assert all(k in conf and widths[k] == conf[k] for k in widths)
    # the dense default is today's seven
    from dynamo_tpu.models import llama

    assert run.served_widths(llama.LlamaConfig.tiny()) == {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 256, "head_dim": 16}


def test_mla_costs_count_the_latent_cache_and_the_experts_touched():
    conf, cfg = mla_conf(), mla.MlaConfig.tiny_moe()
    cost = manifest.module_of(conf, "costs_module", costs)
    w = {"itemsize": 4}
    kv = mla.init_kv_pages(cfg, num_pages=8, page_size=4)
    assert cost.kv_read_bytes(conf, w, 32, 1) == kv.k.nbytes + kv.v.nbytes
    tree = mla.init_params(jax.random.key(0), cfg)
    whole = sum(x.nbytes for x in jax.tree.leaves(tree))
    # many rows touch every expert: the whole tree but the embedding
    assert cost.step_read_bytes(conf, w, 0, 10_000) == pytest.approx(
        whole - tree["embed"].nbytes)
    one_expert = 3 * 64 * 32 * 4
    assert cost.step_read_bytes(conf, w, 0, 1) == pytest.approx(
        whole - tree["embed"].nbytes - 2 * 2 * one_expert)  # 2 of 4, 2 layers


def test_an_mla_moe_configuration_runs_through_the_harness_by_files_alone(
        tmp_path):
    """The CPU rehearsal's whole path: served_widths and check_reference
    take the configuration's own modules, chipbench/ is as committed."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(manifest.ROOT)}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--manifest",
         grown_manifest(tmp_path, mla_conf()), "--workload",
         "mla-tiny-moe-longgen", "--seed", "2200000033", "--seconds", "5",
         "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert last["failed"] == 0 and last["attempted"] > 0
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert notes["serve_up"]["model"] == "mla-tiny-moe"
    assert notes["serve_up"]["widths"]["kv_lora_rank"] == 32
    assert notes["serve_up"]["widths"]["moe_intermediate_size"] == 32
    assert notes["correct"]["widths_as_published"] is True
    assert notes["reference"]["passed"] is True
    assert notes["reference"]["tokens"] == 128
    assert notes["reference"]["max_logprob_drift"] < 1e-3


# -- the dense default through the seam, on the recorded v5e traces ----------


def seam_ctx(**over) -> dict:
    with open(manifest.HERE / "configs" / "qwen2-7b-int8.json") as f:
        hf = json.load(f)
    fused = {"kind": "decode_multi", "n_decode": 63, "tokens": 504}
    ctx = {
        "trace_info": {"wall_start": 100.0, "wall_stop": 100.5},
        "flight": [
            {**fused, "ts": 100.1, "active_pages": 700},
            {**fused, "ts": 100.3, "active_pages": 708, "tokens": 498},
            {"kind": "mixed", "ts": 100.2, "n_decode": 63, "n_prefill": 1,
             "tokens": 64, "active_pages": 5000},
        ],
        "hf": hf, "weights": {"itemsize": 2, "dense_itemsize": 1},
        "page_size": 64, "kernels": True, "peaks": PEAKS,
    }
    ctx.update(over)
    return ctx


def module_from(tmp_path, name: str, source: str):
    path = tmp_path / f"{name}.py"
    path.write_text(source)
    return manifest.module_of({"costs_module": str(path)}, "costs_module",
                              costs)


@pytest.fixture
def hostspans_run(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)
    d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(TESTDATA / "v5e_hostspans_slice.xplane.pb",
                d / "host.xplane.pb")
    hostspans._THIS_RUN.clear()
    yield
    hostspans._THIS_RUN.clear()


def decode_hbm_share_before_the_seam(ctx) -> float:
    """The reader as PR 25 had it, bytes straight from chipbench.costs."""
    dev = ctx["trace"]["modules"]["jit_multi_fn"]
    w, page = ctx["weights"], ctx["page_size"]
    per = []
    for r in ctx["flight"]:
        k = flight.fused_steps(r)
        if k < 1.5:
            continue
        live = max(0.0, r["active_pages"] * page - r["n_decode"] * page / 2)
        per.append(k * costs.decode_step_bytes(
            ctx["hf"], live, w.get("dense_itemsize", 2),
            w.get("itemsize", 2), ctx["kernels"]))
    return (100.0 * (sum(per) / len(per)) * dev["count"] / dev["seconds"]
            / ctx["peaks"]["hbm_bytes_per_s"])


def test_decode_hbm_share_reads_the_same_digits_through_the_seam():
    reduced = trace.reduce(trace.load(
        str(TESTDATA / "v5e_hostspans_slice.xplane.pb")))
    read = manifest.layer_reader("decode_hbm_share")
    ctx = seam_ctx(trace=reduced)
    want = decode_hbm_share_before_the_seam(ctx)
    assert read(ctx) == want  # no ctx["costs"]: the default
    assert read({**ctx, "costs": costs}) == want
    assert 20.0 < want < 100.0
    # PR 23's slice holds mixed steps only: nothing to read, as before
    bare = trace.reduce(trace.load(
        str(TESTDATA / "v5e_decode_slice.xplane.pb")))
    assert "jit_multi_fn" not in bare["modules"]
    assert read(seam_ctx(trace=bare, costs=costs)) is None


def test_paged_attn_hbm_share_reads_the_same_digits_through_the_seam(
        hostspans_run, capsys):
    read = manifest.layer_reader("paged_attn_hbm_share")
    # as PR 25 had it: mean live tokens x costs.kv_bytes_per_token
    live = [700 * 64 - 63 * 32, 708 * 64 - 63 * 32]
    nbytes = sum(live) / len(live) * costs.kv_bytes_per_token(
        seam_ctx()["hf"], 2, True)
    want = 100.0 * nbytes / (0.134632 / 8) / 819e9
    got = read(seam_ctx())
    assert got == read(seam_ctx(costs=costs))
    assert got == pytest.approx(want, rel=1e-4)  # 0.134632 is rounded
    step_s = nbytes * 100.0 / got / 819e9
    assert got == 100.0 * nbytes / step_s / PEAKS["hbm_bytes_per_s"]


@pytest.mark.parametrize("metric,fn", [
    ("decode_hbm_share", "step_read_bytes"),
    ("paged_attn_hbm_share", "kv_read_bytes")])
def test_another_cost_module_answers_or_leaves_the_metric_out(
        metric, fn, tmp_path, hostspans_run, capsys):
    reduced = trace.reduce(trace.load(
        str(TESTDATA / "v5e_hostspans_slice.xplane.pb")))
    read = manifest.layer_reader(metric)
    base = read(seam_ctx(trace=reduced))
    twice = module_from(tmp_path, "twice", (
        "from chipbench import costs\n"
        f"def {fn}(hf, weights, live_tokens, rows, kernels=True):\n"
        "    assert rows == 63\n"
        f"    return 2 * costs.{fn}(hf, weights, live_tokens, rows, kernels)\n"
    ))
    assert read(seam_ctx(trace=reduced, costs=twice)) == pytest.approx(
        2 * base, rel=1e-12)
    no_answer = module_from(tmp_path, "no_answer", (
        f"def {fn}(hf, weights, live_tokens, rows, kernels=True):\n"
        "    return None\n"))
    assert read(seam_ctx(trace=reduced, costs=no_answer)) is None
    silent = module_from(tmp_path, "silent", "X = 1\n")
    assert read(seam_ctx(trace=reduced, costs=silent)) is None
