"""The rehearsal: the whole control flow of one cell at `tiny` on the
CPU. It is never a result: the last line says so."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest


@pytest.mark.parametrize("cell", ["qwen2-longgen", "phi3-chat-closed"])
def test_rehearsal_walks_the_whole_flow_and_is_never_a_result(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(manifest.ROOT)}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell,
         "--seed", "3000000019", "--seconds", "5", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"
    assert last["failed"] == 0
    man = manifest.load()
    want = {m["name"] for m in
            manifest.metrics_of(man, "end_to_end", cell)}
    assert set(last["metrics"]) == want
    assert all(m["value"] > 0 for m in last["metrics"].values())
    notes = {json.loads(x)["note"]: json.loads(x) for x in lines[:-1]}
    assert notes["reference"]["passed"] is True
    assert notes["reference"]["max_logprob_drift"] < 1e-3
    assert notes["correct"]["on_chip"] is False
    # ramp and window are one closed loop: the window was announced at a
    # point of the plan, and every program the ramp loaded is listed
    assert notes["ramp"]["tokens"] > 0 and notes["ramp"]["programs"] > 0
    assert len(notes["programs"]["seen"]) >= notes["ramp"]["programs"]
    assert notes["window"]["idle_after_s"] < 5.0
