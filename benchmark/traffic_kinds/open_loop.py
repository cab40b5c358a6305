"""Traffic kind ``open_loop``: independent users.  Requests are due at
instants fixed before the run (a Poisson process conditioned on its
count), with prompt and output lengths on the quantile grid of a clipped
lognormal.  No two requests share a prefix.

Everything is drawn from ``--seed``: the due instants, which request has
which lengths, which are sampled, the token ids and the sampling seeds.
The lengths are stratified — the lead-in and the window each carry the
whole quantile grid of their own — so every seed sends the same SET of
lengths and only their order and the gaps between arrivals differ.

Parameters (``traffic/<name>.json``): ``rate`` (requests/s), ``prompt_len``
and ``max_tokens`` (``median``, ``sigma``, ``min``, ``max``),
``sampled_share`` with ``sampling`` (the knobs of the sampled requests),
``lead_s`` (the schedule starts that long before the window opens),
``drain_s`` (how long after the window the run goes on reading tokens
before it cuts what is still streaming; a request with no first token by
then has failed, so it is longer than the worst healthy wait).
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def lognormal_grid(n: int, spec: dict) -> list:
    """``n`` lengths: the (i + 0.5)/n quantiles of a lognormal with the
    given median and sigma, clipped to [min, max]."""
    mu, nd = math.log(spec["median"]), NormalDist()
    return [
        int(min(spec["max"], max(spec["min"], round(
            math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)
    ]


def _stretch(rng, n: int, start: float, length: float, traffic: dict,
             vocab: int, tag: str) -> list:
    dues = sorted(start + rng.random() * length for _ in range(n))
    prompts = lognormal_grid(n, traffic["prompt_len"])
    outs = lognormal_grid(n, traffic["max_tokens"])
    rng.shuffle(prompts)
    rng.shuffle(outs)
    sampled = [i < round(n * traffic.get("sampled_share", 0.0)) for i in range(n)]
    rng.shuffle(sampled)
    reqs = []
    for i, due in enumerate(dues):
        payload = {
            "prompt": rng.choices(range(1, vocab), k=prompts[i]),
            "max_tokens": outs[i],
        }
        if sampled[i]:
            payload.update(traffic["sampling"], seed=rng.randrange(1, 2**31))
        reqs.append({"id": f"{tag}{i}", "due": due, "payload": payload})
    return reqs


def make_plan(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    rng = random.Random(seed)
    lead, rate = float(traffic["lead_s"]), float(traffic["rate"])
    # the lead-in and the window each carry their own grid of lengths, so
    # the window's work does not depend on where the seed put the boundary
    reqs = _stretch(rng, round(rate * lead), 0.0, lead, traffic, vocab, "lead")
    reqs += _stretch(rng, round(rate * seconds), lead, seconds, traffic, vocab, "w")
    return {
        "mode": "open", "requests": reqs, "lead_s": lead,
        "drain_s": float(traffic["drain_s"]), "queue_is_load": False,
    }


def run(ctx: dict) -> dict:
    from benchmark import serving

    return serving.run_cell(ctx, make_plan)
