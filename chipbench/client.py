"""The load generator and the client-side clock. One asyncio loop sends
the plan's requests over HTTP (`/v1/completions`, token-id prompts,
streamed) and stamps every SSE chunk as it arrives. Every latency is
taken from the request's *due* time, so a stall is charged to the
requests it delays, and how late the generator itself ran is reported
beside them.

A closed loop runs on through ramp and window without a break. When the
plan's `ramp_tokens` have been delivered the window is announced: it
opens `ramp_lead_s` later."""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field

from chipbench.traffic import Plan, Turn

#: content chunks that arrive closer together than this are one
#: delivery: the frontend writes one chunk per token, so the tokens of
#: one fused dispatch arrive back to back, and whether two of them share
#: a read is the socket's business, not the server's. No decode step of
#: these models is shorter than 5 ms.
COALESCE_S = 0.002


@dataclass
class Result:
    due: float  # perf_counter when the request should have left
    sent: float = 0.0
    measured: bool = False
    status: int | None = None
    error: str | None = None
    want_tokens: int = 0
    prompt_tokens: int = 0
    completion_tokens: int | None = None
    rid: str | None = None
    finish: str | None = None  # the stream's last finish_reason
    left: bool = False  # the client left the stream at the window's end
    cut: bool = False  # ended by the window's end, not by a fault
    #: (arrival time, tokens carried) per content chunk
    chunks: list = field(default_factory=list)
    done: float | None = None

    def tokens_seen(self) -> int:
        return sum(n for _t, n in self.chunks)

    def ok(self) -> bool:
        return (self.status == 200 and self.error is None
                and self.completion_tokens == self.want_tokens
                and self.tokens_seen() == self.want_tokens)

    def ended_by_window(self, t_end: float | None) -> bool:
        """Not a fault: the stream was alive at the window's end, where
        the client left it or the server's own deadline (the window's
        end, `x-request-timeout`) ended it — a 504 before the first
        token, an `error` finish after it. Anything else that is not
        `ok()` is a failure, whenever it ends."""
        return (t_end is not None and self.done >= t_end
                and (self.left or self.status == 504
                     or (self.status == 200 and self.finish == "error")))

    def deliveries(self) -> list:
        out: list = []
        for t, n in self.chunks:
            if out and t - out[-1][2] < COALESCE_S:
                out[-1][1] += n
                out[-1][2] = t
            else:
                out.append([t, n, t])
        return [(t, n) for t, n, _last in out]

    def gaps_ms(self) -> list:
        """(arrival, per-token gap) of every delivery but the first: its
        distance from the one before, divided by the tokens it carries."""
        d = self.deliveries()
        return [
            (d[i][0], (d[i][0] - d[i - 1][0]) * 1000.0 / d[i][1])
            for i in range(1, len(d))
        ]


class Driver:
    """Sends one plan. `t0` is the window's start on perf_counter, None
    during the ramp."""

    def __init__(self, base: str, model: str, mix: dict, seed: int,
                 at_window_start=None, at_window_end=None):
        self.base = base
        self.model = model
        self.sampling = dict(mix.get("sampling") or {"temperature": 0})
        self.seed = int(seed)
        self.results: list[Result] = []
        self.delivered = 0  # tokens, all clients
        self.t0: float | None = None
        self.t_end: float | None = None
        self._n = 0
        self._ramp_tokens = 0
        self._ramped = asyncio.Event()
        self._far = 0.0
        #: called at the window's start and end (counters, the trace)
        self.at_window_start = at_window_start
        self.at_window_end = at_window_end
        #: set at the window's end. A request sent once the window is
        #: announced carries the window's end as its `x-request-timeout`,
        #: so the engine ends it there itself, queued or streaming:
        #: hanging up does not (on the CPU the engine served every left
        #: stream to its last token). One sent earlier cannot know the
        #: window's end; the announcement's lead is long enough for it to
        #: finish first, and one that does not is left at its next chunk.
        self.stop = asyncio.Event()

    async def send(self, session, prompt: list[int], max_tokens: int,
                   due: float) -> Result:
        r = Result(due=due, want_tokens=max_tokens, prompt_tokens=len(prompt))
        self.results.append(r)
        self._n += 1
        body = {
            "model": self.model, "prompt": prompt, "max_tokens": max_tokens,
            "stream": True, "stream_options": {"include_usage": True},
            "ext": {"ignore_eos": True}, **self.sampling,
        }
        if self.sampling.get("temperature"):
            body["seed"] = (self.seed * 1000003 + self._n) % (2**31 - 1)
        r.sent = time.perf_counter()
        deadline = self.t_end if self.t_end is not None else self._far
        headers = {"x-request-timeout": f"{max(0.05, deadline - r.sent):.3f}"}
        try:
            async with session.post(
                self.base + "/v1/completions", json=body, headers=headers
            ) as resp:
                r.status = resp.status
                if resp.status != 200:
                    r.error = (await resp.text())[:300]
                    return r
                async for raw in resp.content:
                    if not raw.startswith(b"data: {"):
                        continue
                    if self.stop.is_set():
                        r.left = True
                        break  # leaving closes the stream: the server aborts
                    now = time.perf_counter()
                    doc = json.loads(raw[6:])
                    if r.rid is None:
                        r.rid = doc.get("id")
                    usage = doc.get("usage")
                    if usage:
                        r.completion_tokens = usage.get("completion_tokens")
                    for choice in doc.get("choices", ()):
                        r.finish = choice.get("finish_reason") or r.finish
                        text = choice.get("text")
                        if text:
                            n = len(text.split())
                            r.chunks.append((now, n))
                            self.delivered += n
                    if (self.delivered >= self._ramp_tokens
                            and not self._ramped.is_set()):
                        self._ramped.set()
        except Exception as e:  # noqa: BLE001 — a failed request is a result
            r.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            r.done = time.perf_counter()
        return r

    async def _client(self, session, turns: list[Turn]) -> None:
        for turn in turns:
            if self.stop.is_set():
                return
            r = await self.send(session, turn.new_ids, turn.max_tokens,
                                time.perf_counter())
            if r.ok():
                # a closed loop counts what completed inside the window
                r.measured = self.t0 is not None and r.done >= self.t0
            elif r.ended_by_window(self.t_end):
                r.cut = True
            else:
                r.measured = True  # a failure, whenever it ended

    async def run(self, plan: Plan, seconds: float) -> float:
        """Run the closed loop through ramp and window, end every stream
        at the window's end, return t0."""
        import aiohttp

        self._ramp_tokens = plan.ramp_tokens
        timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout,
                                         connector=conn) as session:
            t_start = time.perf_counter()
            self._far = (t_start + plan.ramp_max_s + plan.ramp_lead_s
                         + seconds)
            tasks = [asyncio.create_task(self._client(session, turns))
                     for turns in plan.clients]
            try:
                await asyncio.wait_for(self._ramped.wait(), plan.ramp_max_s)
            except asyncio.TimeoutError:
                for t in tasks:
                    t.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
                raise SystemExit(
                    f"chipbench: {self.delivered} of {plan.ramp_tokens} ramp "
                    f"tokens after {plan.ramp_max_s} s: "
                    f"{[r.error for r in self.results if r.error][:3]}")
            self.t0 = time.perf_counter() + plan.ramp_lead_s
            self.t_end = self.t0 + seconds
            await asyncio.sleep(max(0.0, self.t0 - time.perf_counter()))
            if self.at_window_start is not None:
                self.at_window_start(self.t0)
            await asyncio.sleep(max(0.0, self.t_end - time.perf_counter()))
            if self.at_window_end is not None:
                self.at_window_end()
            self.stop.set()
            await asyncio.gather(*tasks)
        return self.t0


def reduce(results: list[Result], t0: float, seconds: float) -> dict:
    """Client-side numbers of one window: what was delivered, and every
    gap that ended, between t0 and t0 + seconds, whichever request it
    belongs to; time to first token of the requests sent in it."""
    t_end = t0 + seconds
    measured = [r for r in results if r.measured]
    failed = [r for r in measured if not r.ok()]
    return {
        "attempted": len(measured),
        "failed": len(failed),
        "failures": [
            {"status": r.status, "error": r.error, "finish": r.finish,
             "want": r.want_tokens, "got": r.completion_tokens,
             "seen": r.tokens_seen(), "at_s": r.done - t0}
            for r in failed[:5]
        ],
        "cut": sum(1 for r in results if r.cut),
        "ttft_ms": [(r.chunks[0][0] - r.due) * 1000.0 for r in results
                    if t0 <= r.sent < t_end and r.chunks],
        "gaps_ms": [g for r in results for t, g in r.gaps_ms()
                    if t0 <= t <= t_end],
        "late_ms": [(r.sent - r.due) * 1000.0 for r in results
                    if t0 <= r.sent < t_end],
        "output_tok_s": sum(
            n for r in results for t, n in r.chunks if t0 <= t <= t_end
        ) / seconds,
    }
