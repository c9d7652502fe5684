"""Serving driver for a configuration whose every block holds a
state-space mixer beside its attention heads, under any `backlog` or
`open` traffic file, through the program's `ServingEngine`: the same
loop, warm-up and bookkeeping as `drivers/serve.py` (imported, not
copied), with this family's weights, arithmetic and reference, and the
engine's state counters read around the window."""

from __future__ import annotations

from dataclasses import replace

import numpy as np

from drivers.serve import (CHECKED_REQUESTS, MODE, PROGRAMS, Observer,
                           serve_until, warm_prompt_lengths)
from harness import arith_state_space as arith
from harness import model_state_space as model
from harness import reference_state_space as reference
from harness import traffic

# Two things are compared, of requests the timed path decoded (prefill
# through chunks, then ticks through both caches), each teacher-forced
# through the reference's full forward pass (its recurrence one token
# at a time).
#
# The TOKENS, as `drivers/serve.py` compares them: (reference's top
# logit - reference logit of the token the engine chose) at EVERY
# generated position of each checked request. This model's logits are
# SMALL (the head's 1/128 and the paths' multipliers: a standard
# deviation of 0.0078 a position), so a gap is taken RELATIVE to the
# standard deviation of the reference's logits at its position. A gap
# is non-zero only where rounding flipped a near tie (4% of positions
# among 261,120 columns). Judged are the MEAN, which goes with the
# square of an error that is everywhere (two whole answers are
# 6,000-8,000 positions; a few hundred leave it a third of noise), and
# the WORST, which tells a fault that is in few places: the first
# positions of an answer read what the prefill's chunks left in the
# slot, and a chunk that did not carry the state is wrong there alone.
#
# The STATE, which the configuration says is float32: what each checked
# request's slot holds when the request finishes (`finished_states`)
# against the reference's S after the last token the request fed, a
# head: |held - S| / |S|. The program's own bf16 inputs move every head
# alike, fast or slow to forget (1.0-1.1% the median head: a state is a
# sum of what unrelated tokens left, which adds up no faster than their
# errors do); a state kept in a lower precision is off by about
# 0.0013 / sqrt(step size x |A|) of its norm, most on the heads that
# forget SLOWEST. So what is judged is the largest gap among the
# slowest quarter of each layer's heads (`slowest_heads`: by step size
# x |A| at rest, from the weights), where the two lie 2.6 x apart and
# not the 1.9 x of the largest over all heads.
#
# Where the limits come from (my chip runs, PR 36; PERF.md section 6
# has every reading). Each went through `judge` below; the faults are
# planted in the serving path at the cell's own sizes by
# `tools/state_space_limits.py`:
#
#   mean relative gap of the tokens         limit MEAN_GAP_TOLERANCE
#     the program, 9 seeds                  0.00055-0.00065
#     the program on int8-rounded weights   0.00293
#     slabs zeroed every tick | rows read from another slot   2.5 | 2.1
#   worst relative gap of the tokens        limit WORST_GAP_TOLERANCE
#     the program, 9 seeds                  0.049-0.068
#     a chunk that starts from zeros        0.51  (its mean 0.00118)
#     the program on int8-rounded weights   0.169
#   largest state gap of the slowest heads  limit SLOW_STATE_GAP_TOLERANCE
#     the program, 5 seeds                  0.0174-0.0185
#     slabs rounded to bf16 every tick      0.047 (its largest head of all)
#     slabs zeroed every tick | rows read from another slot   1.0 | over 0.4
# (over ALL heads the largest reads 0.0201-0.0255 for the program, 9
# seeds, 0.047 and, on another seed, 0.062 for the bf16 slabs and 0.079
# on int8 weights: printed, not judged. The state's limit has 1.6 x of
# room on each side, the least of the three; the program's reading
# moves 3% from seed to seed.)
MEAN_GAP_TOLERANCE = 0.0013
WORST_GAP_TOLERANCE = 0.15
SLOW_STATE_GAP_TOLERANCE = 0.03
SLOW_SHARE = 4          # the slowest 1 / SLOW_SHARE of a layer's heads


def build_engine(cfg, params, t: dict):
    from shallowspeed_tpu.serving.engine import ServingEngine

    e = t["engine"]
    return ServingEngine(
        params, cfg, n_blocks=int(e["cache_blocks"]),
        block_size=int(e["block_size"]), max_slots=int(e["max_slots"]),
        prefill_chunk=int(e["prefill_chunk"]),
        table_bucket=int(e["table_bucket"]), attn_impl=e["attn_impl"],
        prefix_cache=bool(e["prefix_cache"]), lifecycle=False)


def finished_states(eng, held: dict, step) -> None:
    """One `step()` of the engine, and into `held` {request: its slot's
    `ssm` row a layer} for every request that step finished. A request
    finishes by count, so the last tick that ran its row fed the token
    before its last; the tick in flight does not hold it, and its slot
    is written again only by the chunk of the next request admitted to
    it, a step later at the earliest."""
    slot_of = {r.rid: i for i, r in enumerate(eng.slots) if r is not None}
    n = len(eng.request_records)
    step()
    for rec in eng.request_records[n:]:
        held[rec["id"]] = [pool["ssm"][slot_of[rec["id"]]]
                           for pool in eng.pools]


def check_outputs(params, reqs_by_id, results, held, ids, shapes, c, t,
                  **kw) -> tuple[np.ndarray, np.ndarray]:
    """(The relative gaps at every generated position of the checked
    requests, their state gaps (requests, layers, heads)), every request
    padded to the traffic's longest (one shape to compile). The weights
    are the served ones: the reference takes the multipliers back out of
    them first (`folded`)."""
    longest = int(t["prompt_tokens"]["max"]) + int(t["output_tokens"]["max"])
    longest += -longest % reference.Q_BLOCK
    gaps, state = [np.zeros(0)], [np.zeros((0, shapes.layers, shapes.ssm_heads))]
    for rid in ids:
        g, states = reference.teacher_forced(
            params, reqs_by_id[rid]["prompt"], results[rid], shapes,
            model.multipliers(c), float(c["rope_theta"]), folded=True,
            last=len(results[rid]), length=longest, **kw)
        gaps.append(g)
        state.append(reference.state_gaps(held[rid], states)[None])
    return np.concatenate(gaps), np.concatenate(state)


def slowest_heads(params) -> np.ndarray:
    """(layers, heads) bool: the quarter of each layer's heads whose
    state forgets slowest, by step size x |A| with no input
    (softplus(dt_bias) exp(A_log), from the weights)."""
    rest = np.stack([np.logaddexp(0.0, np.asarray(b["mixer"]["dt_bias"]))
                     * np.exp(np.asarray(b["mixer"]["A_log"]))
                     for b in params["blocks"]]).astype(np.float64)
    keep = max(1, rest.shape[1] // SLOW_SHARE)
    return rest <= np.sort(rest, axis=1)[:, keep - 1:keep]


def judge(gaps: np.ndarray, state: np.ndarray, slow: np.ndarray) -> dict:
    """What `correct` rests on, with the numbers beside their limits:
    `gaps` the tokens' relative gaps, `state` the state gaps (requests,
    layers, heads), `slow` the heads that are judged."""
    some = bool(gaps.size and state.size)
    mean_gap = float(gaps.mean()) if some else None
    worst_gap = float(gaps.max()) if some else None
    slow_gap = float(state[:, slow].max()) if some else None
    return {"mean_relative_gap": mean_gap,
            "mean_gap_limit": MEAN_GAP_TOLERANCE,
            "worst_relative_gap": worst_gap,
            "worst_gap_limit": WORST_GAP_TOLERANCE,
            "slow_state_gap": slow_gap,
            "slow_state_gap_limit": SLOW_STATE_GAP_TOLERANCE,
            "within": bool(some and mean_gap <= MEAN_GAP_TOLERANCE
                           and worst_gap <= WORST_GAP_TOLERANCE
                           and slow_gap <= SLOW_STATE_GAP_TOLERANCE),
            # not judged: every head, and the heads that forget fastest
            "largest_state_gap": float(state.max()) if some else None,
            "median_state_gap": float(np.median(state)) if some else None,
            "median_slow_state_gap": float(np.median(state[:, slow]))
            if some else None,
            "share_of_gaps_nonzero": float((gaps > 0).mean())
            if some else None,
            "gaps_checked": int(gaps.size)}


def run(job) -> dict:
    import jax

    c, t, rec = job.config, job.traffic, job.recorder
    shapes = arith.Shapes.from_config(c)
    cfg = model.transformer_config(c, MODE)
    with rec.span("weights"):
        params = model.init_weights_on_device(cfg, job.seed,
                                              model.multipliers(c))
        jax.block_until_ready(params)
    eng = build_engine(cfg, params, t)
    reqs = traffic.requests(t, job.seed, shapes.vocab, job.seconds)
    reqs_by_id = {r["id"]: r for r in reqs}

    with rec.span("warm"):
        warm_rng = np.random.default_rng(0)
        for n in warm_prompt_lengths(t):
            eng.submit(warm_rng.integers(0, shapes.vocab, n), 2)
            eng.run()
    n_warm = len(eng.request_records)
    obs = Observer(eng)
    late: dict[str, float] = {}
    errors: list[str] = []

    # before the window: the ramp of an open loop, or the first requests
    # of a backlog prefilled (set-up the traffic needs, counted as set-up)
    with rec.span("fill"):
        if t["kind"] == "open":
            t_origin = rec.clock() + float(t["ramp_s"])
            i = serve_until(eng, reqs, 0, t_origin, 0.0, rec, obs, late, errors)
        else:
            pre = [r for r in reqs if r["at"] < 0]
            chunk = int(t["engine"]["prefill_chunk"])
            want = eng.counters["prefill_chunks"] \
                + sum(-(-len(r["prompt"]) // chunk) for r in pre)
            i = serve_until(eng, pre, 0, rec.clock(), float("inf"), rec, obs,
                            late, errors,
                            stop=lambda: eng.counters["prefill_chunks"] >= want)
            t_origin = rec.clock()

    # the measured window
    obs.reset()
    first_record = len(eng.request_records)
    first_req = i
    exe_before = eng.executable_counts()
    counters_before = dict(eng.counters)
    job.window_opens(t_origin)
    i = serve_until(eng, reqs, i, t_origin, float(job.seconds), rec, obs, late,
                    errors, on_loop=job.on_loop)
    window_s = rec.clock() - t_origin
    job.window_closes()
    pending_at_end = eng.pending()
    compiles = sum(eng.executable_counts().values()) - sum(exe_before.values())
    records = eng.request_records[first_record:]
    preempted = eng.counters["preempted"]
    # per decode tick of the window, from the engine's own counters
    ticks = max(1, eng.counters["ticks"] - counters_before["ticks"])
    per_tick = {k: (eng.counters[k] - counters_before[k]) / ticks
                for k in ("state_rows", "state_bytes", "blocks_read")
                if k in eng.counters}
    shapes = replace(shapes, state_rows=per_tick.get("state_rows"))
    peak = job.memory_peak()

    # outside the window: serve on until enough requests have finished
    # to check, then free the cache and hold them against the reference
    with rec.span("check"):
        held: dict = {}
        while len(held) < CHECKED_REQUESTS and eng.pending():
            finished_states(eng, held, eng.step)
        done = sorted(held)
        pick = np.random.default_rng(job.seed).permutation(len(done))
        ids = [done[k] for k in pick[:CHECKED_REQUESTS]]
        results = {rid: np.asarray(eng.results[rid]) for rid in ids}
        eng.pools = None
        wrong_len = [r["id"] for r in records
                     if r["tokens_out"] != reqs_by_id[r["id"]]["max_new"]]
        del eng
        verdict = judge(*check_outputs(params, reqs_by_id, results, held, ids,
                                       shapes, c, t), slowest_heads(params))

    finished = [r for r in records if "tpot_ms" in r]
    e2e = {"serve_out_tok_s": obs.emitted / window_s}
    if finished:
        e2e["tpot_ms"] = arith.median([r["tpot_ms"] for r in finished])
    in_window = reqs[first_req:i]
    layers = {
        "programs": PROGRAMS, "shapes": shapes, "window_s": window_s,
        "steps": obs.steps, "itl_ms": obs.itl_ms, "records": records,
        "late_ms": late, "due_in_window": [r["id"] for r in in_window],
        "slots": int(t["engine"]["max_slots"]), "compiles": compiles,
        "block_size": int(t["engine"]["block_size"]),
    }
    return {
        "correct": bool(len(ids) == CHECKED_REQUESTS and verdict["within"]
                        and not errors and not wrong_len),
        "attempted": len(in_window),
        "failed": len(errors) + len(wrong_len),
        "end_to_end": e2e,
        "memory_peak_bytes": peak,
        "notes": {**verdict, "checked": ids,
                  "finished_in_window": len(records), "window_s": window_s,
                  "pending_at_end": pending_at_end, "preempted": preempted,
                  "ticks_in_window": ticks, "per_tick": per_tick,
                  "itl_samples": len(obs.itl_ms), "errors": errors[:5]},
        "layers": layers,
    }
