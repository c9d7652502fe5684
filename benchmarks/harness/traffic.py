"""One general traffic generator. A traffic mix is a data file of
parameters; this module turns it into the requests or batches of a run.

Sizes and arrival offsets come from the file's own `generator_seed`, so
every run of a cell does the same work whatever `--seed` it is given;
`--seed` draws the token ids (and, in the drivers, the weights)."""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np


def load_traffic(path) -> dict:
    with open(Path(path)) as f:
        return json.load(f)


def _draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "loguniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def arrival_offsets(t: dict, horizon_s: float) -> np.ndarray:
    """Due times in seconds relative to the start of the measured window.
    `open`: exponential gaps at `rate_per_s` from `-ramp_s` to the
    horizon, in bursts of `burst` requests that share one instant (the
    mean rate stays `rate_per_s`). `backlog`: `n_requests` requests, the
    first `prefilled_before_window` before the window and the rest all
    due as it opens."""
    rng = np.random.default_rng([int(t["generator_seed"]), 1])
    if t["kind"] == "backlog":
        n, pre = int(t["n_requests"]), int(t["prefilled_before_window"])
        return np.concatenate([np.full(pre, -1.0), np.zeros(n - pre)])
    a = t["arrivals"]
    burst = int(a.get("burst", 1))
    span = float(t["ramp_s"]) + float(horizon_s)
    n_bursts = int(span * a["rate_per_s"] / burst * 2) + 16
    starts = np.cumsum(rng.exponential(burst / a["rate_per_s"], n_bursts))
    starts = starts[starts < span] - float(t["ramp_s"])
    return np.repeat(starts, burst)


def requests(t: dict, seed: int, vocab: int, horizon_s: float) -> list:
    """The requests of a serving run, in due order: dicts with `id`, `at`
    (seconds from the window's start; negative = before it), `prompt`
    (int32 ids drawn from `seed`) and `max_new`. With `reask` > 1 each
    prompt is asked that many times (ids repeat, so a prefix cache can
    hit); otherwise no two prompts share a prefix beyond chance."""
    at = arrival_offsets(t, horizon_s)
    n = len(at)
    reask = int(t.get("reask", 1))
    rng_p = np.random.default_rng([int(t["generator_seed"]), 2])
    rng_o = np.random.default_rng([int(t["generator_seed"]), 3])
    n_docs = -(-n // reask)
    p_len = np.repeat(_draw_lengths(rng_p, t["prompt_tokens"], n_docs),
                      reask)[:n]
    o_len = _draw_lengths(rng_o, t["output_tokens"], n)
    ids = np.random.default_rng([int(seed), 7])
    docs = [ids.integers(0, vocab, int(l), dtype=np.int32)
            for l in p_len[::reask]]
    return [{"id": f"q{i}", "at": float(at[i]), "prompt": docs[i // reask],
             "max_new": int(o_len[i])} for i in range(n)]


def train_batches(t: dict, seed: int, vocab: int, n_chips: int,
                  n_distinct: int = 8) -> list:
    """`n_distinct` (tokens, targets) batches of packed sequences, cycled
    by the driver: `seqs_per_chip` x chips rows of `seq_len` ids from
    `seed`, each row's target its next token."""
    rng = np.random.default_rng([int(seed), 7])
    rows, length = int(t["seqs_per_chip"]) * n_chips, int(t["seq_len"])
    out = []
    for _ in range(n_distinct):
        full = rng.integers(0, vocab, (rows, length + 1), dtype=np.int32)
        out.append((np.ascontiguousarray(full[:, :-1]),
                    np.ascontiguousarray(full[:, 1:])))
    return out
