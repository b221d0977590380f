"""One general traffic generator, driven by a mix file (``traffic/<mix>.json``).

Two kinds of mix:

* ``sessions``: open-loop chat.  Sessions arrive with gamma inter-arrival
  gaps (coefficient of variation ``arrival_cv``); each opens with one of a
  few shared system prompts picked by Zipf popularity (or none), and
  turn k+1's prompt is turn k's prompt, a synthetic answer and a new user
  message, due ``think_s`` after turn k.  Every prompt is known ahead, so
  the loop stays open.
* ``batch``: an offline queue, every request due at the start.

The *shape* of the traffic (lengths, arrival times, turn counts, prompt
choice) is drawn once from the mix's ``shape_seed``; ``--seed`` draws the
token ids and, for sessions, permutes which session takes which arrival
slot.  The arrival times stay as drawn, so every seed puts the same
requests due in the window; a batch queue keeps its order, since a window
drains only its head.  So every seed offers the same work, and a seed never
changes the load.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Item:
    """One request of the schedule.  ``due`` is seconds from window start
    (negative: lead-in).  ``prompt`` is a list of token ids."""
    rid: int
    due: float
    prompt: List[int]
    max_new: int
    session: int = -1


def lognormal_ints(rng, spec, n):
    """Lognormal lengths with the given median and sigma, clipped to
    [min, max] (min defaults to 1)."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec.get("min", 1), spec["max"]).astype(int)


def gamma_gaps(rng, mean, cv, n):
    """Gamma inter-arrival gaps with the given mean and coefficient of
    variation (cv 1 is Poisson; cv 2 is bursty, as in BurstGPT)."""
    k = 1.0 / (cv * cv)
    return rng.gamma(k, mean / k, n)


def zipf_choice(rng, n_items, s, n):
    p = 1.0 / np.arange(1, n_items + 1) ** s
    return rng.choice(n_items, size=n, p=p / p.sum())


def seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**32, seed // 2**32, 7])


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_seq: int) -> List[Item]:
    """The schedule of a run: items sorted by due time."""
    if mix["kind"] == "sessions":
        return _sessions(mix, seed, seconds, vocab, max_seq)
    if mix["kind"] == "batch":
        return _batch(mix, seed, vocab, max_seq)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def _sessions(mix, seed, seconds, vocab, max_seq):
    shape = np.random.default_rng(mix["shape_seed"])
    lead = float(mix["lead_in_s"])
    span = lead + float(seconds)
    n = max(1, int(round(mix["rate"] * span)))
    gaps = gamma_gaps(shape, 1.0 / mix["rate"], mix["arrival_cv"], n)
    gaps *= span / gaps.sum()          # exactly the nominal rate over the span
    sp = mix.get("system_prompts") or {"count": 0, "tokens": 0}
    t = mix["turns"]
    lo, hi = mix["think_s"]
    cap = min(int(mix["session_max_tokens"]), max_seq - 1)
    # n sessions whose first turn fits: which ones fit is the shape's
    # business, so every seed fills every arrival slot
    sessions = []
    while len(sessions) < n:
        k = int(min(shape.geometric(1.0 / t["mean"]), t["max"]))
        s = dict(sys=int(zipf_choice(shape, sp["count"], sp["zipf_s"], 1)[0])
                 if sp["count"] else 0,
                 user=lognormal_ints(shape, mix["user_tokens"], k),
                 new=lognormal_ints(shape, mix["max_new_tokens"], k),
                 think=shape.uniform(lo, hi, k))
        if sp["tokens"] + s["user"][0] + s["new"][0] <= cap:
            sessions.append(s)

    order = seed_rng(seed)
    perm_s = order.permutation(n)
    starts = -lead + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    sys_prompts = [order.integers(0, vocab, sp["tokens"]).tolist()
                   for _ in range(max(sp["count"], 1))]
    items: List[Item] = []
    for slot, si in enumerate(perm_s):
        s = sessions[si]
        ctx = list(sys_prompts[s["sys"]])
        due = float(starts[slot])
        for k in range(len(s["new"])):
            ctx = ctx + order.integers(0, vocab, int(s["user"][k])).tolist()
            new = int(s["new"][k])
            if len(ctx) + new > cap or due >= seconds:
                break
            items.append(Item(0, due, list(ctx), new, session=slot))
            # the next turn carries a synthetic answer of this turn's length
            ctx = ctx + order.integers(0, vocab, new).tolist()
            due += float(s["think"][k])
    items.sort(key=lambda it: it.due)
    for i, it in enumerate(items):
        it.rid = i
    return items


def _batch(mix, seed, vocab, max_seq):
    shape = np.random.default_rng(mix["shape_seed"])
    n = int(mix["requests"])
    plen = lognormal_ints(shape, mix["prompt_tokens"], n)
    new = lognormal_ints(shape, mix["max_new_tokens"], n)
    new = np.minimum(new, max_seq - 1 - plen)
    order = seed_rng(seed)
    due = -float(mix["lead_in_s"])
    # the queue keeps the shape seed's order: a window drains only its
    # head, so another order would change the work the window sees
    return [Item(i, due, order.integers(0, vocab, int(plen[i])).tolist(),
                 int(new[i])) for i in range(n)]
