"""KV offload A/B: multi-turn conversations with the host-DRAM tier on
vs off, same traffic, same (deliberately small) HBM page pool.

The reference's headline offload claim — TTFT +40% with system-memory KV
offload on a multi-turn workload (architecture.md:95, "10 multi-turn
convs x 80 users, prefix caching on") — comes from exactly this shape:
conversations cycle faster than the device pool can hold them, so each
turn's prefix has been evicted by the time the user returns. Without a
host tier the prefix recomputes; with one it onboards back from DRAM.

This harness boots ONE single-process HTTP server per mode (the tier is
an engine feature — no fleet needed), drives U users x T turns
round-robin (each turn appends the assistant reply and re-sends the
grown conversation, so consecutive turns share a true chat-template
prefix), and reports per-turn TTFT percentiles for turns >= 2 (turn 1 is
cold in both modes).

CPU smoke: defaults — validates MECHANICS only. On a tiny CPU model the
economics invert (recomputing a few dozen tokens costs ~nothing, while
each eviction pays a device->host extraction), so expect speedup < 1
there; the claim under test needs real prefill costs, i.e. the TPU run:
--model llama3-1b --dtype bfloat16 --page-size 16 --num-pages 192
--max-context 2048 --users 8 --turns 4 --turn-chars 400
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

from benchmarks._procs import ManagedProc as Proc
from benchmarks._procs import cli as _cli
from benchmarks._procs import free_port as _free_port
from benchmarks._procs import pct as _shared_pct


def _pct(values, q):
    v = _shared_pct(values, q)
    return None if v is None else round(v, 2)


async def _one_turn(session, url, model, messages, osl):
    """POST the conversation, stream the reply; returns (ttft_ms, text)."""
    t0 = time.perf_counter()
    ttft = None
    text = []
    async with session.post(
        f"{url}/v1/chat/completions",
        json={"model": model, "messages": messages, "stream": True,
              "max_tokens": osl},
    ) as resp:
        resp.raise_for_status()
        async for raw in resp.content:
            line = raw.decode().strip()
            if not line.startswith("data:") or line == "data: [DONE]":
                continue
            if ttft is None:
                ttft = (time.perf_counter() - t0) * 1000
            try:
                delta = json.loads(line[5:])["choices"][0]["delta"]
                if delta.get("content"):
                    text.append(delta["content"])
            except Exception:  # noqa: BLE001 — error frames end the turn
                break
    return ttft, "".join(text)


async def _drive(url, model, args) -> dict:
    import aiohttp

    import random

    r = random.Random(11)
    convs = [
        [{"role": "user",
          "content": "".join(chr(97 + r.randrange(26))
                             for _ in range(args.turn_chars))}]
        for _ in range(args.users)
    ]
    ttfts_by_turn: dict[int, list[float]] = {}
    async with aiohttp.ClientSession() as session:
        for turn in range(args.turns):
            # round-robin: every user takes their turn before anyone's
            # next turn — by the time user u returns, the other users'
            # prefills have churned the small HBM pool past u's pages
            for conv in convs:
                ttft, reply = await _one_turn(
                    session, url, model, conv, args.osl
                )
                if ttft is not None:
                    ttfts_by_turn.setdefault(turn + 1, []).append(ttft)
                conv.append({"role": "assistant", "content": reply or "."})
                conv.append({
                    "role": "user",
                    "content": "".join(chr(97 + r.randrange(26))
                                       for _ in range(args.turn_chars)),
                })
    warm = [t for turn, ts in ttfts_by_turn.items() if turn >= 2 for t in ts]
    return {
        "ttft_ms_by_turn": {
            str(k): {"p50": _pct(v, 0.5), "p95": _pct(v, 0.95)}
            for k, v in sorted(ttfts_by_turn.items())
        },
        "warm_turns_ttft_ms": {
            "p50": _pct(warm, 0.5), "p95": _pct(warm, 0.95),
            "n": len(warm),
        },
    }


def run_mode(args, host_tier: bool) -> dict:
    hport = _free_port()
    argv = _cli(
        "run", "in=http", "out=jax", "--model", args.model,
        "--dtype", args.dtype, "--page-size", str(args.page_size),
        "--num-pages", str(args.num_pages),
        "--max-context", str(args.max_context), "--port", str(hport),
    )
    if host_tier:
        argv += ["--host-kv-bytes", str(args.host_kv_bytes)]
    if args.decode_steps is not None:
        argv += ["--decode-steps", str(args.decode_steps)]
    server = Proc("server", argv)
    try:
        server.wait_for("listening on", timeout=900)
        out = asyncio.run(
            _drive(f"http://127.0.0.1:{hport}", args.model, args)
        )
        out["host_tier"] = host_tier
        return out
    except BaseException:
        import sys

        print(f"--- server log ({server.log_path}):", file=sys.stderr)
        try:
            with open(server.log_path) as f:
                print("\n".join(f.read().splitlines()[-30:]),
                      file=sys.stderr)
        except OSError:
            pass
        raise
    finally:
        server.stop()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="KV offload A/B (host tier)")
    p.add_argument("--model", default="tiny")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--page-size", type=int, default=4, dest="page_size")
    p.add_argument("--num-pages", type=int, default=48, dest="num_pages")
    p.add_argument("--max-context", type=int, default=192,
                   dest="max_context")
    p.add_argument("--host-kv-bytes", type=int, default=1 << 30,
                   dest="host_kv_bytes")
    p.add_argument("--users", type=int, default=6)
    p.add_argument("--turns", type=int, default=3)
    p.add_argument("--turn-chars", type=int, default=24, dest="turn_chars")
    p.add_argument("--osl", type=int, default=8)
    p.add_argument("--decode-steps", type=int, default=None,
                   dest="decode_steps",
                   help="worker decode fusion (steps per dispatch)")
    args = p.parse_args(argv)

    results = {
        "workload": {
            "users": args.users, "turns": args.turns,
            "turn_chars": args.turn_chars, "model": args.model,
            "num_pages": args.num_pages, "page_size": args.page_size,
        },
        "modes": {
            "no_tier": run_mode(args, host_tier=False),
            "host_tier": run_mode(args, host_tier=True),
        },
    }
    off = results["modes"]["no_tier"]["warm_turns_ttft_ms"]
    on = results["modes"]["host_tier"]["warm_turns_ttft_ms"]
    if off.get("p50") and on.get("p50"):
        results["offload_ttft_speedup_p50"] = round(
            off["p50"] / max(on["p50"], 1e-9), 3
        )
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
