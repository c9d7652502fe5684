"""The traffic generator is a pure function of its file: every seed does
the same work, in the same order, on other token ids."""
import numpy as np
import pytest

from harness import traffic

BENCH = __import__("pathlib").Path(__file__).resolve().parent.parent
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json")
               if traffic.load_traffic(p)["driver"] == "serve")


def _serving(name):
    return traffic.load_traffic(BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_sizes_and_arrivals_for_every_seed(name):
    t = _serving(name)
    a = traffic.requests(t, 1, 1000, 20.0)
    b = traffic.requests(t, 2**31 + 12345, 1000, 20.0)
    assert [(r["id"], r["at"], len(r["prompt"]), r["max_new"]) for r in a] \
        == [(r["id"], r["at"], len(r["prompt"]), r["max_new"]) for r in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    again = traffic.requests(t, 1, 1000, 20.0)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_the_files_limits(name):
    t = _serving(name)
    reqs = traffic.requests(t, 3, 1000, 30.0)
    p, o = t["prompt_tokens"], t["output_tokens"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in reqs)
    assert all(o["min"] <= r["max_new"] <= o["max"] for r in reqs)
    assert [r["at"] for r in reqs] == sorted(r["at"] for r in reqs)


def test_a_longer_horizon_only_appends():
    t = _serving("chat")
    short = traffic.requests(t, 1, 1000, 10.0)
    long = traffic.requests(t, 1, 1000, 30.0)
    assert [(r["at"], r["max_new"]) for r in short] \
        == [(r["at"], r["max_new"]) for r in long[:len(short)]]
    rate = len([r for r in long if r["at"] >= 0]) / 30.0
    assert rate == pytest.approx(t["arrivals"]["rate_per_s"], rel=0.35)


def test_bursts_keep_the_mean_rate_and_reask_repeats_prompts():
    t = dict(_serving("chat"), arrivals={"rate_per_s": 4.0, "burst": 8})
    at = traffic.arrival_offsets(t, 200.0)
    assert len(at) % 8 == 0 and len(set(at[:8])) == 1
    assert len(at) / (200.0 + t["ramp_s"]) == pytest.approx(4.0, rel=0.2)
    reqs = traffic.requests(dict(t, reask=4), 1, 1000, 10.0)
    assert (reqs[0]["prompt"] == reqs[3]["prompt"]).all()
    assert len(reqs[0]["prompt"]) != len(reqs[4]["prompt"]) \
        or (reqs[0]["prompt"] != reqs[4]["prompt"]).any()


def test_backlog_queues_everything_as_the_window_opens():
    t = _serving("doc-batch")
    at = traffic.arrival_offsets(t, 30.0)
    pre = t["prefilled_before_window"]
    assert len(at) == t["n_requests"]
    assert (at[:pre] < 0).all() and (at[pre:] == 0).all()
    longest = t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
    assert longest <= 4096          # the sliding window never binds


def test_train_batches_shift_targets_by_one():
    t = traffic.load_traffic(BENCH / "traffic" / "sft.json")
    (tok, tgt), *_ = traffic.train_batches(t, 2**31 + 7, 50304, 1)
    assert tok.shape == (t["seqs_per_chip"], t["seq_len"]) == tgt.shape
    assert (tok[:, 1:] == tgt[:, :-1]).all()
