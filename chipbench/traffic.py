"""The one traffic generator. A mix is a JSON file of parameters
(chipbench/traffic/<mix>.json); this module turns (mix, seed) into a
plan: which client sends what, in which order. No mix needs code of its
own. Only the closed loop is here: the open-loop mixes of PERF.md
section 7 bring their branch with the cell that proves them.

Sizes are drawn from distributions given as data (`draw`): `const`
(`value`), `uniform_int` (`min`..`max`, both ends included) and
`lognormal` (`median`, `sigma` of the underlying normal, rounded to
whole numbers and clipped to `min`..`max`).

Steadiness: every size of a plan — prompt lengths, answer lengths, the
cut of each client's first answer — is drawn from the mix's own
`shape_seed`, client by client, so every run of a cell offers the same
requests in the same order. The run's `--seed` draws the token ids (and,
in the client, the sampling seeds) and nothing else: two seeds differ in
content, not in work.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

#: ids 0..9 stay out of prompts: 0 is the engine's default eos id
FIRST_ID = 10


@dataclass
class Turn:
    """One request of a client."""
    new_ids: list[int]
    max_tokens: int


@dataclass
class Plan:
    clients: list[list[Turn]] = field(default_factory=list)
    #: the window is announced when the clients have been delivered this
    #: many tokens: a point of the plan, not of the clock, so that the
    #: same requests fall into every run's window however long the
    #: programs took to load ...
    ramp_tokens: int = 0
    #: ... and opens this much later: as long as a request can live less
    #: the window, so that every request alive at the window's end was
    #: sent knowing when that is
    ramp_lead_s: float = 0.0
    #: a run whose ramp_tokens take longer than this fails
    ramp_max_s: float = 600.0


def draw(dist: dict, rng: np.random.Generator, n: int) -> list[int]:
    """n whole numbers from a distribution given as data."""
    kind = dist["dist"]
    if kind == "const":
        return [int(dist["value"])] * n
    if kind == "uniform_int":
        return [int(v) for v in rng.integers(dist["min"], dist["max"] + 1, n)]
    if kind == "lognormal":
        raw = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
        return [int(v) for v in
                np.clip(np.rint(raw), dist["min"], dist["max"])]
    raise ValueError(f"unknown distribution {kind!r}")


def plan(mix: dict, seed: int, vocab: int) -> Plan:
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    shape = np.random.default_rng(int(mix.get("shape_seed", 0)))
    ids = np.random.default_rng(int(seed) % (2**63))
    n_clients = int(mix["clients"])
    # enough requests per client to outlast ramp and window at any speed
    # the chip reaches; a client that runs out simply stops
    per = int(mix["requests_per_client"])
    p = Plan(ramp_tokens=int(mix["ramp_tokens"]),
             ramp_lead_s=float(mix["ramp_lead_s"]),
             ramp_max_s=float(mix.get("ramp_max_s", 600.0)))
    prompts = draw(mix["prompt_tokens"], shape, n_clients * per)
    outs = draw(mix["output_tokens"], shape, n_clients * per)
    phase = shape.uniform(0.0, 1.0, n_clients)
    if "first_prompt_tokens" in mix:
        # the burst that fills an idle engine is set-up: one prompt length
        # there keeps the programs it touches few (PERF.md section 4)
        prompts[::per] = draw(mix["first_prompt_tokens"], shape, n_clients)
    for c in range(n_clients):
        turns = []
        for j in range(per):
            k = c * per + j
            out = outs[k]
            if j == 0 and mix.get("phase_first_request", False):
                # start in steady state: the first answer is cut to a
                # random fraction so completions come staggered
                out = max(4, int(out * phase[c]))
            turns.append(Turn(
                [int(v) for v in ids.integers(FIRST_ID, vocab, prompts[k])],
                out))
        p.clients.append(turns)
    return p
