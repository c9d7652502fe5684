"""Serving driver: any configuration under any `open` or `backlog`
traffic file, through the program's `ServingEngine`.

The loop is `serve.py`'s own (submit what is due, then `eng.step()`),
with each request timed from when it was due. From the program it reads
`request_records`, `counters`, `executable_counts()`, and the live
requests' token counts in `slots`; everything else is the benchmark's."""

from __future__ import annotations

import time

import numpy as np

from harness import arith, model, reference, traffic

# names of the engine's compiled programs in the device trace
PROGRAMS = {"decode": r"^jit__decode_tick\(", "prefill": r"^jit__prefill_chunk\("}
MODE = "serving"
# Largest allowed (reference's top logit - reference logit of the token
# the engine chose). The engine computes in bf16 where the reference is
# float32: over 16-24 layers its logits come out within a few hundredths
# (of a spread of about 1), so its choice is the reference's own or a
# near tie (worst gap seen on the chip in PR 24: 0.054). Weights in 8 bits,
# or a cache read at the wrong position, give gaps of 0.5 and more. The
# worst gap of a run is printed on its `notes` line.
LOGIT_GAP_TOLERANCE = 0.15
CHECKED_REQUESTS = 2
CHECKED_POSITIONS = 32


def build_engine(cfg, params, t: dict):
    from shallowspeed_tpu.serving.engine import ServingEngine

    e = t["engine"]
    return ServingEngine(
        params, cfg, n_blocks=int(e["cache_tokens"]) // int(e["block_size"]) + 1,
        block_size=int(e["block_size"]), max_slots=int(e["max_slots"]),
        prefill_chunk=int(e["prefill_chunk"]),
        table_bucket=int(e["table_bucket"]), attn_impl=e["attn_impl"],
        prefix_cache=bool(e["prefix_cache"]), lifecycle=False)


def warm_prompt_lengths(t: dict) -> list[int]:
    """Prompt lengths that, each served alone for two tokens, compile
    every program the traffic can reach: the prefill chunk at each block
    table width its prompts give, and the decode tick at each width its
    contexts grow through. The widths are the engine's own bucketing."""
    from shallowspeed_tpu.serving.engine import table_width

    e = t["engine"]
    bs, base = int(e["block_size"]), int(e["table_bucket"])
    blocks = lambda n: -(-n // bs)
    width = lambda n_tok: table_width(blocks(n_tok), base)
    p_lo, p_hi = int(t["prompt_tokens"]["min"]), int(t["prompt_tokens"]["max"])
    longest = p_hi + int(t["output_tokens"]["max"]) - 1
    lengths, decode_done = [], set()
    for w in sorted({width(n) for n in range(p_lo, p_hi + 1, bs)} | {width(p_hi)}):
        n = min(w * bs, p_hi)       # the longest prompt of this width
        lengths.append(n)
        decode_done.add(width(n + 1))
    for w in sorted({width(n + 1) for n in range(p_lo, longest, bs)}
                    | {width(longest)}):
        if w not in decode_done:
            lengths.append((w // 2) * bs)   # its first decode opens width w
    return lengths


class Observer:
    """Per-step bookkeeping on the benchmark's clock: tokens emitted,
    gaps between a request's tokens, slot occupancy, live cache size."""

    def __init__(self, eng):
        self.eng = eng
        self.seen: dict[str, tuple[int, float]] = {}
        self.n_done = len(eng.request_records)
        self.reset()

    def reset(self):
        self.emitted = 0
        self.itl_ms: list[float] = []
        self.steps: list[dict] = []

    def _note(self, rid: str, n: int, now: float):
        prev_n, prev_t = self.seen.get(rid, (0, None))
        if n > prev_n:
            if prev_t is not None:
                gap = (now - prev_t) * 1e3 / (n - prev_n)
                self.itl_ms.extend([gap] * (n - prev_n))
            self.seen[rid] = (n, now)
            self.emitted += n - prev_n

    def after_step(self, t0: float, t1: float, prefill_before: int):
        eng = self.eng
        decoding = live_tokens = 0
        for r in eng.slots:
            if r is None:
                continue
            self._note(r.rid, len(r.generated), t1)
            if r.phase == "decode":
                decoding += 1
                live_tokens += r.written
        for rec in eng.request_records[self.n_done:]:
            self._note(rec["id"], rec["tokens_out"], t1)
            self.seen.pop(rec["id"], None)
        self.n_done = len(eng.request_records)
        self.steps.append({
            "t0": t0, "t1": t1, "decoding": decoding,
            "live_tokens": live_tokens,
            "prefill": eng.counters["prefill_chunks"] > prefill_before})


def serve_until(eng, reqs, start_i, t_origin, t_end, rec, obs, late, errors,
                stop=None, on_loop=None):
    """`serve.py`'s replay loop from request `start_i` until the clock
    passes `t_end` (seconds relative to `t_origin`) or `stop()` holds.
    Returns the index of the next request not yet submitted."""
    i, clock = start_i, rec.clock
    while True:
        now = clock() - t_origin
        if now >= t_end or (stop is not None and stop()):
            return i
        if on_loop is not None:
            on_loop(now)
        if i < len(reqs) and reqs[i]["at"] <= now:
            with rec.span("submit"):
                while i < len(reqs) and reqs[i]["at"] <= now:
                    r = reqs[i]
                    i += 1
                    try:
                        eng.submit(r["prompt"], r["max_new"], rid=r["id"])
                        late[r["id"]] = (clock() - t_origin - r["at"]) * 1e3
                    except (TypeError, ValueError) as e:
                        errors.append(f"{r['id']}: {type(e).__name__}: {e}")
        if eng.pending():
            before = eng.counters["prefill_chunks"]
            t0 = clock()
            with rec.span("step"):
                eng.step()
            obs.after_step(t0, clock(), before)
        elif i < len(reqs):
            with rec.span("wait"):
                time.sleep(min(0.05, max(0.0, reqs[i]["at"] - now)))
        elif t_end == float("inf"):
            return i
        else:
            with rec.span("wait"):
                time.sleep(min(0.05, t_end - now))


def check_outputs(params, reqs_by_id, results, ids, shapes, c) -> float:
    """Worst gap over the checked positions of the checked requests."""
    worst = 0.0
    for rid in ids:
        gaps = reference.chosen_logit_gaps(
            params, reqs_by_id[rid]["prompt"], results[rid], shapes,
            c["program"], float(c["rope_theta"]), last=CHECKED_POSITIONS)
        worst = max(worst, float(gaps.max()))
    return worst


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
    obs = Observer(eng)
    late: dict[str, float] = {}
    errors: list[str] = []

    # before the window: the ramp of an open loop, or the first documents
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
    job.window_opens(t_origin)
    i = serve_until(eng, reqs, i, t_origin, float(job.seconds), rec, obs, late,
                    errors, on_loop=job.on_loop)
    window_s = rec.clock() - t_origin
    job.window_closes()
    pending_at_end = eng.pending()
    compiles = sum(eng.executable_counts().values()) - sum(exe_before.values())
    records = eng.request_records[first_record:]
    preempted = eng.counters["preempted"]
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
        worst_gap = check_outputs(params, reqs_by_id, results, ids, shapes, c)

    finished = [r for r in records if "tpot_ms" in r]
    e2e = {"serve_out_tok_s": obs.emitted / window_s}
    if finished:
        e2e["tpot_ms"] = arith.median([r["tpot_ms"] for r in finished])
    in_window = reqs[first_req:i]
    return {
        "correct": bool(len(ids) == CHECKED_REQUESTS
                        and worst_gap <= LOGIT_GAP_TOLERANCE
                        and not errors and not wrong_len),
        "attempted": len(in_window),
        "failed": len(errors) + len(wrong_len),
        "end_to_end": e2e,
        "memory_peak_bytes": peak,
        "notes": {"worst_logit_gap": worst_gap, "checked": ids,
                  "finished_in_window": len(records), "window_s": window_s,
                  "pending_at_end": pending_at_end, "preempted": preempted,
                  "itl_samples": len(obs.itl_ms), "errors": errors[:5]},
        "layers": {
            "programs": PROGRAMS, "shapes": shapes, "window_s": window_s,
            "steps": obs.steps, "itl_ms": obs.itl_ms, "records": records,
            "late_ms": late, "due_in_window": [r["id"] for r in in_window],
            "slots": int(t["engine"]["max_slots"]), "compiles": compiles,
        },
    }
