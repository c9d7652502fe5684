"""Where the limits of `drivers/serve_state_space.py`'s comparison come
from, measured once on the chip: faults PLANTED IN THE SERVING PATH at
the cell's own sizes, each judged by the driver's own comparison
(`check_outputs`, `judge`), which has to call every one of them not
correct and the path left alone correct.

    python3 benchmarks/tools/state_space_limits.py --seed 7

Requests of the cell's own lengths go through the program's
`ServingEngine` at the cell's own engine parameters (prefill through
chunks, then ticks through both caches: the very programs the cell
times, most of their rows empty), two requests a fault, all in one run.
The programs are the cell's, untouched (nothing compiles anew); a fault
is what happens to its requests' rows of the mixers' slabs BETWEEN two
ticks, every tick:

    none         nothing (the path as it is: has to read as a cell run)
    bf16_state   the rows rounded to bf16: the nearest precision below
                 the float32 the configuration states for the state
    zeroed       the rows zeroed: a tick that does not carry the state
    swapped      the two requests' rows exchanged: a tick that reads
                 another slot's row
    no_carry     the rows zeroed while their prompt is between two of
                 its chunks: a chunk that starts from zeros at
                 pos0 > 0 (its two requests are those whose prompts'
                 last chunk is shortest)

and, in a second run, `int8`: the path as it is on weights whose every
matrix is rounded to int8, the nearest precision below the bf16 they
are served in, judged against the reference on the weights as served.

One JSON line a fault: `judge`'s numbers beside their limits, `within`
last. Not part of a benchmark run: `run.py` never reads this file."""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

STATE_FAULTS = ("none", "bf16_state", "zeroed", "swapped", "no_carry")
PER_FAULT = 2


def main() -> int:
    import jax
    import jax.numpy as jnp

    from drivers import serve_state_space as driver
    from harness import arith_state_space as arith
    from harness import model_state_space as model
    from harness import traffic
    from shallowspeed_tpu.serving.cache import state_leaves

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=str(
        HERE / "configs" / "falcon-h1-34b-instruct.json"))
    ap.add_argument("--traffic", default=str(HERE / "traffic" / "long-gen.json"))
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--faults", default=",".join(STATE_FAULTS + ("int8",)))
    args = ap.parse_args()
    c = model.load_config(args.config)
    t = traffic.load_traffic(args.traffic)
    cfg = model.transformer_config(c, "serving")
    shapes = arith.Shapes.from_config(c)
    m = model.multipliers(c)
    chunk = int(t["engine"]["prefill_chunk"])
    slots = int(t["engine"]["max_slots"])
    reqs = traffic.requests(t, args.seed, shapes.vocab, 30.0)
    by_id = {r["id"]: r for r in reqs}
    # prompts of more than one chunk, those with the shortest last chunk
    # first: a state that was not carried shows most where few tokens
    # follow it
    long = [r["id"] for r in sorted(reqs, key=lambda r: len(r["prompt"]) % chunk)
            if len(r["prompt"]) > chunk and len(r["prompt"]) % chunk]
    faults = args.faults.split(",")

    def weights():
        return model.init_weights_on_device(cfg, args.seed, m)

    @partial(jax.jit, donate_argnums=0)
    def between_ticks(slabs, kind, source):
        """Every layer's slabs with the faults' rows changed: `kind` a
        row (0 as it is, 1 rounded to bf16, 2 zeroed), then row i taken
        from row source[i]."""
        def rows(v, k):
            return (kind == k).reshape((-1,) + (1,) * (v.ndim - 1))

        fi = jnp.finfo(jnp.bfloat16)
        out = []
        for slab in slabs:
            s = slab["ssm"]
            s = jnp.where(rows(s, 1), jax.lax.reduce_precision(
                s, fi.nexp, fi.nmant), s)
            s = jnp.where(rows(s, 2), 0.0, s)[source]
            conv = jnp.where(rows(slab["conv"], 2), 0, slab["conv"])[source]
            out.append({"ssm": s, "conv": conv})
        return out

    def serve(params, assigned: dict):
        """{request: fault} through one engine; what each request chose
        and what its slot held when it finished."""
        eng = driver.build_engine(cfg, params, t)
        for rid in assigned:
            eng.submit(by_id[rid]["prompt"], by_id[rid]["max_new"], rid=rid)
        held: dict = {}
        plain = all(f in ("none", "int8") for f in assigned.values())
        while eng.pending():
            driver.finished_states(eng, held, eng.step)
            if plain:
                continue
            kind = np.zeros(slots, np.int32)
            source = np.arange(slots, dtype=np.int32)
            live = {r.rid: r for r in eng.slots if r is not None}
            for rid, r in live.items():
                f = assigned[rid]
                kind[r.slot] = {"bf16_state": 1, "zeroed": 2}.get(f, 0)
                if f == "no_carry" and r.phase == "prefill":
                    kind[r.slot] = 2
            pair = [r.slot for rid, r in live.items()
                    if assigned[rid] == "swapped"]
            if len(pair) == 2:
                source[pair] = pair[::-1]
            new = between_ticks([state_leaves(p) for p in eng.pools],
                                kind, source)
            eng.pools = [{**p, **n} for p, n in zip(eng.pools, new)]
        results = {rid: np.asarray(eng.results[rid]) for rid in assigned}
        eng.pools = None
        return results, held

    def report(fault, params, ids, results, held):
        verdict = driver.judge(*driver.check_outputs(
            params, by_id, results, held, ids, shapes, c, t),
            driver.slowest_heads(params))
        print(json.dumps({"fault": fault, "seed": args.seed, "requests": ids,
                          **verdict}), flush=True)

    params = weights()
    state = [f for f in faults if f in STATE_FAULTS]
    # as many faults a run as the slots hold, two requests each
    per_run = max(1, slots // PER_FAULT)
    for i in range(0, len(state), per_run):
        free = [r["id"] for r in reqs]
        assigned, of_fault = {}, {}
        for f in state[i:i + per_run]:
            pool = [rid for rid in (long if f == "no_carry" else free)
                    if rid not in assigned]
            of_fault[f] = pool[:PER_FAULT]
            assigned.update({rid: f for rid in of_fault[f]})
        results, held = serve(params, assigned)
        for f, ids in of_fault.items():
            report(f, params, ids, results, held)

    if "int8" in faults:
        def to_int8_and_back(w):
            if w.ndim < 2 or w.dtype == jnp.float32:   # norms, per-head vectors
                return w
            f = w.astype(jnp.float32)
            scale = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / 127.0
            return (jnp.round(f / scale) * scale).astype(w.dtype)

        # rounded leaf by leaf into the buffers it came from, and made
        # again from the seed for the reference: two copies of the
        # weights do not fit the chip
        leaves, tree = jax.tree_util.tree_flatten(params)
        del params
        rounded = jax.jit(to_int8_and_back, donate_argnums=0)
        for i in range(len(leaves)):
            leaves[i] = rounded(leaves[i])
        ids = [r["id"] for r in reqs[:PER_FAULT]]
        results, held = serve(jax.tree_util.tree_unflatten(tree, leaves),
                              {rid: "int8" for rid in ids})
        del leaves
        report("int8", weights(), ids, results, held)
    return 0


if __name__ == "__main__":
    sys.exit(main())
