"""Serving driver for a configuration with window and full attention
layers mixed and routed experts, under any `backlog` or `open` traffic
file, through the program's `ServingEngine`: the same loop, warm-up and
bookkeeping as `drivers/serve.py` (imported, not copied), with this
family's weights, arithmetic and reference, a pool a layer group
(`engine.cache_blocks` of the traffic file: {"full": ..., "window": ...}),
and the engine's expert and window counters read around the window."""

from __future__ import annotations

from dataclasses import replace

import numpy as np

from drivers.serve import (CHECKED_REQUESTS, MODE, PROGRAMS, Observer,
                           serve_until, warm_prompt_lengths)
from harness import arith_window_experts as arith
from harness import model_window_experts as model
from harness import reference_window_experts as reference
from harness import traffic

# The comparison is `drivers/serve_latent_experts.py`'s, for its reason:
# (reference's top logit - reference logit of the token the engine chose)
# at the last CHECKED_POSITIONS generated positions of each checked
# request, teacher-forced. The choice of 8 of 128 experts is
# discontinuous in the hidden state, so near-tied scores send a token of
# the bf16 program through other experts than the float32 reference's,
# and the worst gap says nothing (0.56-0.89 for the right program, 1.06
# against a reference with 8-bit weights); the limits are on the SHARE
# of positions whose gap passes the dense limit and on the MEAN gap,
# over 2 x 256 positions. Either failing fails the run. Readings on the
# chip (PERF.md §6, PR 33): the program as it is reads a share of
# 0.043-0.066 and a mean of 0.016-0.028 (the cell's runs over 14 seeds,
# and `tools/window_experts_limits.py`'s forward pass without a cache);
# against the reference with every matrix rounded to int8 it reads
# 0.186 and 0.084. Each limit lies between its two readings, about as
# far from either in ratio (1.8 x the program's largest, 0.6 x the
# rounded reference's). The gaps are a tenth of the latent
# configuration's: 5 layers, not 8, and each sub-layer's output is
# normed before it is added, so a swapped expert moves the stream less.
CHECKED_POSITIONS = 256
GAP_LIMIT = 0.15
OVER_SHARE_TOLERANCE = 0.12
MEAN_GAP_TOLERANCE = 0.05


def build_engine(cfg, params, t: dict):
    from shallowspeed_tpu.serving.engine import ServingEngine

    e = t["engine"]
    return ServingEngine(
        params, cfg, n_blocks={k: int(v) for k, v in e["cache_blocks"].items()},
        block_size=int(e["block_size"]), max_slots=int(e["max_slots"]),
        prefill_chunk=int(e["prefill_chunk"]),
        table_bucket=int(e["table_bucket"]), attn_impl=e["attn_impl"],
        prefix_cache=bool(e["prefix_cache"]), lifecycle=False)


class WindowObserver(Observer):
    """`Observer`, and of each step the tokens the decoding slots' window
    layers can see: sum over slots of min(context, window)."""

    def __init__(self, eng, window: int):
        self.window = window
        super().__init__(eng)

    def after_step(self, t0, t1, prefill_before):
        super().after_step(t0, t1, prefill_before)
        self.steps[-1]["window_tokens"] = sum(
            min(r.written, self.window) for r in self.eng.slots
            if r is not None and r.phase == "decode")


def check_outputs(params, reqs_by_id, results, ids, shapes, c, t) -> np.ndarray:
    """The gaps at the checked positions of the checked requests, every
    request padded to the traffic's longest (one shape to compile)."""
    longest = int(t["prompt_tokens"]["max"]) + int(t["output_tokens"]["max"])
    longest += -longest % reference.Q_BLOCK
    embed_scale = float(c["hidden_size"]) ** 0.5 if c["mup_enabled"] else 1.0
    return np.concatenate([np.zeros(0)] + [reference.chosen_logit_gaps(
        params, reqs_by_id[rid]["prompt"], results[rid], shapes,
        model.layer_pattern(c), float(c["rope_theta"]),
        float(c["route_scale"]), embed_scale, last=CHECKED_POSITIONS,
        length=longest) for rid in ids])


def run(job) -> dict:
    import jax

    c, t, rec = job.config, job.traffic, job.recorder
    shapes = arith.Shapes.from_config(c)
    cfg = model.transformer_config(c, MODE)
    with rec.span("weights"):
        params = model.init_weights_on_device(cfg, job.seed)
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
    obs = WindowObserver(eng, shapes.window)
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
                for k in ("experts_touched", "max_load", "released",
                          "window_blocks", "full_blocks",
                          "blocks_read_window", "blocks_read_full")}
    decoding = [s for s in obs.steps if s["decoding"]]
    seen = sum(s["window_tokens"] for s in decoding) \
        / max(1, sum(s["live_tokens"] for s in decoding))
    shapes = replace(shapes, experts_touched=per_tick["experts_touched"],
                     windowed_share=seen if decoding else None)
    peak = job.memory_peak()

    # outside the window: serve on until enough requests have finished
    # to check, then free the cache and hold them against the reference
    with rec.span("check"):
        while len(eng.request_records) < n_warm + CHECKED_REQUESTS \
                and eng.pending():
            eng.step()
        done = [r["id"] for r in eng.request_records[n_warm:]]
        pick = np.random.default_rng(job.seed).permutation(len(done))
        ids = [done[k] for k in pick[:CHECKED_REQUESTS]]
        results = {rid: np.asarray(eng.results[rid]) for rid in ids}
        eng.pools = None
        wrong_len = [r["id"] for r in records
                     if r["tokens_out"] != reqs_by_id[r["id"]]["max_new"]]
        del eng
        gaps = check_outputs(params, reqs_by_id, results, ids, shapes, c, t)

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
    over_share = float((gaps > GAP_LIMIT).mean()) if gaps.size else None
    mean_gap = float(gaps.mean()) if gaps.size else None
    return {
        "correct": bool(len(ids) == CHECKED_REQUESTS
                        and over_share <= OVER_SHARE_TOLERANCE
                        and mean_gap <= MEAN_GAP_TOLERANCE
                        and not errors and not wrong_len),
        "attempted": len(in_window),
        "failed": len(errors) + len(wrong_len),
        "end_to_end": e2e,
        "memory_peak_bytes": peak,
        "notes": {"share_of_gaps_over_limit": over_share,
                  "mean_logit_gap": mean_gap,
                  "worst_logit_gap": float(gaps.max()) if gaps.size else None,
                  "share_of_gaps_nonzero": float((gaps > 0).mean())
                  if gaps.size else None,
                  "gaps_checked": int(gaps.size), "checked": ids,
                  "finished_in_window": len(records), "window_s": window_s,
                  "pending_at_end": pending_at_end, "preempted": preempted,
                  "ticks_in_window": ticks, "per_tick": per_tick,
                  "windowed_share": shapes.windowed_share,
                  "itl_samples": len(obs.itl_ms), "errors": errors[:5]},
        "layers": layers,
    }
