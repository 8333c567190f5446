"""The fast decode pipeline carried across hosts: overlap_decode,
mixed_steps and the fused decode scan run on multi-process SPMD
meshes. `EngineConfig.force_multihost` makes a
single-process engine take the multi-controller code paths (replicated
decode outputs, addressable-shard readbacks, lockstep-safe scheduling)
so CPU tests pin the contract deterministically: per-process token
streams BIT-IDENTICAL to the single-host path, greedy and sampled."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams


def _make(**overrides):
    base = EngineConfig.for_tests()
    cfg = EngineConfig(**{**base.__dict__, **overrides})
    return JaxEngine(cfg)


def _workload():
    """Greedy AND sampled requests with stop tokens and staggered
    max_tokens so finishes land mid-wave (rollback-heavy, the shape the
    single-host overlap parity tests pin)."""
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(6):
        prompt = [int(x) for x in rng.integers(1, 200, 3 + (i % 4))]
        sampled = i % 2 == 1
        reqs.append(
            (
                f"r{i}",
                prompt,
                SamplingParams(
                    temperature=0.7 if sampled else 0.0,
                    top_p=0.9 if sampled else 1.0,
                    seed=200 + i,
                    max_tokens=4 + 3 * (i % 3),
                    stop_token_ids=(13,) if i in (2, 5) else (),
                ),
            )
        )
    # one long steady wave so the overlap pipeline and the fused scan
    # actually engage after the staggered finishes drain
    reqs.append(
        (
            "long",
            [5, 17, 42],
            SamplingParams(max_tokens=24, ignore_eos=True),
        )
    )
    return reqs


def _run(eng, reqs):
    for rid, prompt, s in reqs:
        eng.add_request(rid, prompt, s)
    return eng.run_to_completion()


def test_force_multihost_takes_multiproc_paths(cpu_mesh_devices):
    eng = _make(topology="tp=2,dp=2", force_multihost=True)
    assert eng._multiproc is True
    assert eng._rep_sharding is not None
    # the pipeline stays ON: no multi-host auto-off anymore
    assert eng._overlap_enabled and eng._mixed_enabled


def test_speculation_still_disables_pipeline_multihost(cpu_mesh_devices):
    """The speculation auto-offs survive the multi-host lift: prompt
    lookup needs host tokens, so the pipeline yields to it regardless
    of topology."""
    eng = _make(
        topology="tp=2,dp=2", force_multihost=True, spec_ngram=3,
    )
    assert eng._multiproc is True
    assert not eng._overlap_enabled
    assert not eng._mixed_enabled


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_multihost_pipeline_bit_exact_vs_single_host(
    decode_steps, cpu_mesh_devices
):
    """THE acceptance pin: the full pipeline (overlap + mixed + the fused
    scan) under the forced multi-host mesh produces per-request token streams
    bit-identical to the same engine without the multi-host paths, and
    to the fully synchronous single-host reference."""
    reqs = _workload()
    ref_sync = _run(
        _make(topology="tp=2,dp=2", overlap_decode=False,
              mixed_steps=False, decode_steps=1),
        reqs,
    )
    ref_host = _run(
        _make(topology="tp=2,dp=2", decode_steps=decode_steps), reqs,
    )
    mh = _make(
        topology="tp=2,dp=2", force_multihost=True,
        decode_steps=decode_steps,
    )
    got = _run(mh, reqs)
    assert got == ref_host
    assert got == ref_sync
    assert mh.metrics.overlap_hits > 0, "overlap never engaged"
    if decode_steps > 1:
        assert mh.compiles_by_kind.get("decode_multi"), "never fused"


def test_multihost_streams_bit_exact_across_an_admission(cpu_mesh_devices):
    """More requests than slots, so a row that ends at its max_tokens
    hands its slot on while a dispatch is in flight: the successor is
    admitted ahead and its first piece rides the mixed step launched
    ahead, its token fed on device (replicated ids through the gather).
    Every process decides that from replicated scheduler state alone:
    the forced multi-host streams equal the single-host ones and the
    synchronous reference's."""
    queued = dict(max_seqs=2, decode_buckets=(1, 2), decode_steps=1)
    reqs = [
        (rid, prompt, SamplingParams(
            temperature=s.temperature, top_p=s.top_p, seed=s.seed,
            max_tokens=s.max_tokens, ignore_eos=True))
        for rid, prompt, s in _workload()[:5]
    ]
    ref_sync = _run(
        _make(topology="tp=2,dp=2", overlap_decode=False,
              mixed_steps=False, **queued),
        reqs,
    )
    ref_host = _run(_make(topology="tp=2,dp=2", **queued), reqs)
    mh = _make(topology="tp=2,dp=2", force_multihost=True, **queued)
    got = _run(mh, reqs)
    assert got == ref_host
    assert got == ref_sync
    m = mh.metrics
    assert m.mixed_dispatches >= 3 and m.overlap_rollbacks == 0
    assert m.overlap_hits >= m.decode_dispatches + m.mixed_dispatches - 2


def test_multihost_mesh_report_carries_logical_groups(cpu_mesh_devices):
    """/v1/debug/mesh under the forced multi-host mesh: multiprocess
    flag set, non-replicated logical param groups, rule provenance."""
    eng = _make(topology="tp=2,dp=2", force_multihost=True)
    rep = eng.mesh_report()
    assert rep["multiprocess"] is True
    assert rep["mesh"]["shape"] == {"dp": 2, "sp": 1, "ep": 1, "tp": 2}
    groups = rep["param_groups"]
    sharded = {
        k: g for k, g in groups.items() if k != "replicated"
    }
    assert sharded, "a tp=2 engine must shard some param group"
    assert any(g["logical"] for g in sharded.values())
    assert ["heads", "tp"] in rep["logical_axis_rules"]


def test_topology_serves_end_to_end_over_http(cpu_mesh_devices):
    """The --topology knob, end to end: a registry model built with
    `topology="tp=2,dp=2"` serves completions through the real HTTP
    frontend, and GET /v1/debug/mesh shows its non-replicated param
    groups with logical-axis names (tentpole 3 acceptance)."""
    import aiohttp

    from dynamo_tpu.engine.async_engine import AsyncEngineRunner
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.service import local_pipeline
    from dynamo_tpu.model_card import ModelDeploymentCard

    async def main():
        engine = _make(topology="tp=2,dp=2")
        assert engine.config.tp == 2 and engine.config.dp == 2
        runner = AsyncEngineRunner(engine)
        runner.start()
        manager = ModelManager()
        card = ModelDeploymentCard(
            name="tiny", tokenizer={"kind": "byte"}, context_length=32
        )
        manager.add("tiny", local_pipeline(card, runner))
        svc = HttpService(manager, host="127.0.0.1", port=0)
        await svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        try:
            async with aiohttp.ClientSession() as s:
                body = {
                    "model": "tiny",
                    "prompt": "ab",
                    "max_tokens": 5,
                    "ext": {"ignore_eos": True},
                }
                async with s.post(f"{base}/v1/completions", json=body) as r:
                    assert r.status == 200, await r.text()
                    data = await r.json()
                    assert data["usage"]["completion_tokens"] == 5
                async with s.get(f"{base}/v1/debug/mesh") as r:
                    assert r.status == 200
                    doc = await r.json()
            mine = doc["engines"][engine.debug_name]
            assert mine["mesh"]["shape"] == {
                "dp": 2, "sp": 1, "ep": 1, "tp": 2
            }
            sharded = {
                k: g
                for k, g in mine["param_groups"].items()
                if k != "replicated"
            }
            assert sharded, "tp=2 topology must shard param groups"
            assert any(g.get("logical") for g in sharded.values())
        finally:
            await svc.stop()
            runner.stop()

    asyncio.run(main())
