"""FT on hardware: SIGKILL the prefill worker while a DEVICE-plane KV
pull is in flight; the decode worker must fall back and finish the
request.

The CPU fault-tolerance suite covers prefill death on the HOST transfer
path only (tests/fault_tolerance/test_scenarios.py) because the CPU
backend's transfer server cannot survive a cross-process pull (see
disagg/device_transfer.py docstring). This script is the TPU complement:
a real cross-process pull over the PjRt transfer fabric, interrupted by
killing the sender the moment the receiver logs "device KV pull start".

Mirrors the reference's kill-injection methodology
(/root/reference/tests/fault_tolerance/scenarios.py) applied to the NIXL
analog plane. Writes artifacts/tpu/ft_device_kill.json.

Needs one device per worker process: both workers are engine processes,
and nothing assigns a device to a child yet (ROADMAP R4/R5), so on a
one-chip host the second fails at start-up.

Usage: python scripts/tpu_ft_device_kill.py
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._procs import ManagedProc, cli, free_port  # noqa: E402

MODEL = ["--model", "llama3-1b", "--dtype", "bfloat16", "--page-size", "16",
         "--num-pages", "256", "--max-context", "2048"]
ISL = 512  # ~16 MB of 1b-shape KV: the pull is a real multi-frame transfer
OSL = 8


def wait_log(proc: ManagedProc, needle: str, timeout: float) -> bool:
    """Tight poll (2 ms) so the kill lands inside the pull window."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        with open(proc.log_path) as f:
            if needle in f.read():
                return True
        time.sleep(0.002)
    return False


def main() -> None:
    out: dict = {"platform": None, "ok": False}
    procs: list[ManagedProc] = []
    try:
        import jax

        out["platform"] = jax.devices()[0].platform
        fport, hport = free_port(), free_port()
        fabric = ManagedProc("fabric", cli("fabric", "--port", str(fport)))
        procs.append(fabric)
        fabric.wait_for("listening|fabric server on")
        decode = ManagedProc(
            "decode",
            cli("run", "in=dyn", "out=jax", *MODEL,
                "--disagg", "--max-local-prefill", "64",
                "--transfer-timeout", "10",
                "--fabric", f"127.0.0.1:{fport}"),
        )
        procs.append(decode)
        decode.wait_for(r"worker \w+ up", timeout=900)
        prefill = ManagedProc(
            "prefill",
            cli("run", "in=dyn", "out=jax", *MODEL, "--role", "prefill",
                "--fabric", f"127.0.0.1:{fport}"),
        )
        procs.append(prefill)
        prefill.wait_for(r"prefill worker \w+ up", timeout=900)
        frontend = ManagedProc(
            "frontend",
            cli("run", "in=http", "out=dyn",
                "--fabric", f"127.0.0.1:{fport}", "--port", str(hport)),
        )
        procs.append(frontend)
        frontend.wait_for("listening on")
        frontend.wait_for("model attached", timeout=120)

        # Warm the compile caches end to end (remote path included) so the
        # measured request's timing is dominated by the transfer, not XLA.
        t_warm = time.time()
        status0, _ = _request(hport, "w" * ISL, OSL, timeout=900)
        out["warm"] = {"status": status0, "s": round(time.time() - t_warm, 1)}
        _clear_kv(hport)

        # The measured request: kill the sender at pull start.
        res: dict = {}

        def _one():
            t0 = time.time()
            try:
                status, ntok = _request(hport, "x" * ISL, OSL, timeout=120)
            except Exception as e:  # noqa: BLE001
                status, ntok = -1, 0
                res["error"] = repr(e)
            res.update(status=status, tokens=ntok,
                       latency_s=round(time.time() - t0, 2))

        t_req_start = time.time()
        t = threading.Thread(target=_one)
        t.start()
        saw_pull = wait_log(decode, "device KV pull start", 90)
        kill_t = time.time()
        if saw_pull:
            prefill.proc.send_signal(signal.SIGKILL)
        t.join(timeout=180)
        out["saw_pull_start"] = saw_pull
        out["request"] = res
        dlog = open(decode.log_path).read()
        out["pull_failed_logged"] = "device KV pull failed" in dlog
        out["local_fallback_logged"] = (
            "failed/timed out; local fallback" in dlog
        )
        out["ok"] = bool(
            saw_pull
            and res.get("status") == 200
            and res.get("tokens", 0) > 0
            and (out["pull_failed_logged"] or out["local_fallback_logged"])
        )
        if saw_pull and "latency_s" in res:
            out["kill_to_done_s"] = round(
                t_req_start + res["latency_s"] - kill_t, 2
            )
    finally:
        for p in reversed(procs):
            try:
                p.stop()
            except Exception:  # noqa: BLE001
                pass
    print(json.dumps(out, indent=1))
    sys.exit(0 if out["ok"] else 1)


def _request(port: int, text: str, osl: int, timeout: float) -> tuple[int, int]:
    body = json.dumps({
        "model": "llama3-1b",
        "messages": [{"role": "user", "content": text}],
        "max_tokens": osl, "stream": False,
    }).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        data = json.loads(resp.read())
        usage = data.get("usage") or {}
        return resp.status, usage.get("completion_tokens", 0)


def _clear_kv(port: int) -> None:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/clear_kv_blocks", data=b"{}",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.status == 200


if __name__ == "__main__":
    main()
