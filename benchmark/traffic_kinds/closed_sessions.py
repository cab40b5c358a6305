"""Traffic kind ``closed_sessions``: callers that each wait for a reply.
``clients`` clients run multi-turn sessions back to back.  A session
picks one of ``system_prompts`` seeded system prompts (Zipf popularity),
then takes ``turns`` turns; a turn appends a user message to the history,
asks for ``max_tokens`` greedy tokens, and the tokens the server returned
join the history; the client thinks ``think_s`` before the next turn.  A
session ends early when its context would pass ``max_context``.

Every range in the parameters is ``[low, high]``; values are taken from
an even grid over the range, so every seed sends the same set of lengths
and ``--seed`` deals them out: which session has how many turns, every
length and think time, who gets which, and every token id.

``prime: true`` sends each system prompt once (one output token) before
the clients start, so the shared prefixes are in the radix tree as in a
deployment that has been up for a while; with ``gate_tokens`` the first
is then sent again asking for that many tokens, and the clients start
``gate_overlap_s`` after it, while it still decodes.  ``queue_is_load:
true`` marks a mix ABOVE the knee, where callers outnumber what the engine
holds and waiting is the load: a caller still waiting for its first token
when the run ends is then no failure (starvation shows in tokens/s).
"""

from __future__ import annotations

import random


def _grid(rng, n: int, low, high, integer: bool = True) -> list:
    """``n`` values evenly spread over [low, high], shuffled."""
    vals = [low + (high - low) * (i + 0.5) / n for i in range(n)]
    if integer:
        vals = [int(min(high, max(low, round(v)))) for v in vals]
    rng.shuffle(vals)
    return vals


def make_plan(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    rng = random.Random(seed)
    n_sys, sys_len = traffic["system_prompts"], traffic["system_prompt_len"]
    systems = [rng.choices(range(1, vocab), k=sys_len) for _ in range(n_sys)]
    n = traffic["clients"]
    zipf = [1.0 / (i + 1) ** traffic.get("zipf_s", 1.0) for i in range(n_sys)]
    clients = [[] for _ in range(n)]
    # every ROUND (the j-th session of every client) carries the whole grid
    # of its own, so what a run reaches of each client's list is the same
    # set of lengths whatever the seed
    for _ in range(traffic["sessions_per_client"]):
        # popularity as exact shares of the sessions, not as draws
        which = [i for i, w in enumerate(zipf) for _ in range(round(n * w / sum(zipf)))]
        which = (which + [0] * n)[:n]
        rng.shuffle(which)
        n_turns = _grid(rng, n, *traffic["turns"])
        total = sum(n_turns)
        users = _grid(rng, total, *traffic["user_len"])
        outs = _grid(rng, total, *traffic["max_tokens"])
        thinks = _grid(rng, total, *traffic["think_s"], integer=False)
        for c in range(n):
            context, turns = sys_len, []
            for _ in range(n_turns[c]):
                user, out, think = users.pop(), outs.pop(), thinks.pop()
                if context + user + out > traffic["max_context"]:
                    break
                context += user + out
                turns.append({"user": rng.choices(range(1, vocab), k=user),
                              "max_tokens": out, "think_s": think})
            clients[c].append({"system": systems[which[c]], "turns": turns})
    primers = []
    if traffic.get("prime"):
        primers = [{"prompt": s, "max_tokens": 1} for s in systems]
        if traffic.get("gate_tokens"):
            primers.append({"prompt": systems[0], "max_tokens": traffic["gate_tokens"]})
    return {
        "mode": "closed", "clients": clients, "primers": primers,
        "primer_overlap_s": float(traffic.get("gate_overlap_s", 0.0)),
        "stagger_s": float(traffic.get("stagger_s", 0.0)),
        "lead_s": float(traffic["lead_s"]), "drain_s": float(traffic["drain_s"]),
        "queue_is_load": bool(traffic.get("queue_is_load", False)),
    }


def run(ctx: dict) -> dict:
    from benchmark import serving

    return serving.run_cell(ctx, make_plan)
