"""One general generator of request traffic, from a mix's data file.

Copied from the program's ``serving/loadgen.py`` (``LoadConfig`` /
``make_trace``: seeded, open loop, Poisson arrivals, uniform lengths, a
Zipf-weighted pool of shared prefixes, multi-turn sessions) so that no
later PR can move the yardstick, with one change: **every seed gets the
same set of sizes and arrivals, in another order**.  The inter-arrival
gaps and the (prompt, output) lengths are drawn once from the mix's own
``mix_seed`` for ``rate x duration`` requests; the run's ``--seed`` only
permutes them and draws the token ids.  So two runs differ in what is
asked when, not in how much work there is.

A mix file's ``requests`` group:

    rate_rps      arrivals per second (Poisson; gaps rescaled to fill
                  the duration exactly)
    prompt_len    [lo, hi] uniform
    output_len    [lo, hi] uniform
    mix_seed      seed of the fixed set of gaps and lengths
    prefix_pool, prefix_len, zipf_alpha   shared prefixes (0 = none)
    turns, turn_gap_s, turn_tokens        follow-up turns (1 = none)
"""

from __future__ import annotations

import numpy as np


def make_trace(requests: dict, seed: int, duration_s: float,
               vocab_size: int) -> list[dict]:
    """``[{"arrival_s", "prompt", "max_new_tokens"[, "session", "turn"]}]``
    sorted by arrival, all due inside ``[0, duration_s)``."""
    rate = float(requests["rate_rps"])
    if rate <= 0 or duration_s <= 0:
        raise ValueError("rate_rps and the duration must be positive")
    n = max(1, round(rate * duration_s))
    fixed = np.random.default_rng(int(requests.get("mix_seed", 0)))
    gaps = fixed.exponential(1.0, n + 1)
    gaps = gaps[:n] * (duration_s / gaps.sum())  # last gap closes the window
    p_lo, p_hi = requests["prompt_len"]
    o_lo, o_hi = requests["output_len"]
    plens = fixed.integers(p_lo, p_hi + 1, n)
    olens = fixed.integers(o_lo, o_hi + 1, n)

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    gaps = gaps[rng.permutation(n)]
    order = rng.permutation(n)
    plens, olens = plens[order], olens[order]

    pool_n = int(requests.get("prefix_pool", 0))
    pool = probs = None
    if pool_n > 0:
        prefix_len = int(requests["prefix_len"])
        if prefix_len < 1:
            raise ValueError("prefix_pool needs prefix_len >= 1")
        pool = [rng.integers(0, vocab_size, prefix_len, dtype=np.int32)
                for _ in range(pool_n)]
        ranks = np.arange(1, pool_n + 1, dtype=np.float64)
        probs = ranks ** -float(requests.get("zipf_alpha", 1.1))
        probs /= probs.sum()
        # the same multiset of prefix picks for every seed, permuted
        picks = fixed.choice(pool_n, size=n, p=probs)[rng.permutation(n)]

    trace = []
    t = 0.0
    for i in range(n):
        t += float(gaps[i])
        if pool is not None:
            prefix = pool[int(picks[i])]
            suffix = rng.integers(
                0, vocab_size, max(int(plens[i]) - prefix.size, 1),
                dtype=np.int32,
            )
            prompt = np.concatenate([prefix, suffix])
        else:
            prompt = rng.integers(0, vocab_size, int(plens[i]), dtype=np.int32)
        trace.append({
            "arrival_s": t, "prompt": prompt,
            "max_new_tokens": int(olens[i]),
        })
    turns = int(requests.get("turns", 1))
    if turns > 1:
        trace = _add_turns(requests, trace, fixed, rng, vocab_size, duration_s)
    return trace


def _add_turns(requests, base, fixed, rng, vocab_size, duration_s):
    """Each base request seeds a session: follow-up turns arrive
    ~turn_gap_s later with a prompt that extends the prior turn's."""
    gap = float(requests.get("turn_gap_s", 0.25))
    lo, hi = requests.get("turn_tokens", [4, 12])
    o_lo, o_hi = requests["output_len"]
    turns = int(requests["turns"])
    n = len(base)
    gaps = fixed.exponential(gap, (n, turns))[rng.permutation(n)]
    extra = fixed.integers(lo, hi + 1, (n, turns))[rng.permutation(n)]
    outs = fixed.integers(o_lo, o_hi + 1, (n, turns))[rng.permutation(n)]
    out = []
    for i, r in enumerate(base):
        sid = f"s{i}"
        out.append({**r, "session": sid, "turn": 0})
        t, prompt = r["arrival_s"], r["prompt"]
        for turn in range(1, turns):
            t += float(gaps[i, turn])
            if t >= duration_s:
                break
            prompt = np.concatenate([
                prompt,
                rng.integers(0, vocab_size, int(extra[i, turn]),
                             dtype=np.int32),
            ])
            out.append({
                "arrival_s": t, "prompt": prompt,
                "max_new_tokens": int(outs[i, turn]),
                "session": sid, "turn": turn,
            })
    out.sort(key=lambda r: r["arrival_s"])
    return out
