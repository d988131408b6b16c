"""The traffic generator: the same seed gives the same inputs; stratified
draws hold the distribution's mix in every block; a late submit does not
shorten a request's time to first token."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest, serve_runner, traffic


def mix(name):
    return json.load(open(os.path.join(manifest.BENCH_DIR, "traffic",
                                       name + ".json")))


@pytest.mark.parametrize("name", ["chat-steady", "docs-backlog"])
def test_same_seed_same_requests(name):
    m = mix(name)
    a = traffic.serve_requests(m, 32000, 5, 20.0, n_closed=40)
    b = traffic.serve_requests(m, 32000, 5, 20.0, n_closed=40)
    c = traffic.serve_requests(m, 32000, 6, 20.0, n_closed=40)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    for r in a:
        assert m["prompt_len"]["min"] <= len(r.prompt) <= m["prompt_len"]["max"]
        assert m["output_len"]["min"] <= r.max_new <= m["output_len"]["max"]
        assert r.prompt.min() >= 1          # 0 is the pad id
    # distinct prompts: no two share their first page
    assert len({tuple(r.prompt[:16]) for r in a}) == len(a)


def test_stratified_blocks_hold_the_same_lengths_whatever_the_seed():
    d = mix("chat-steady")["prompt_len"]
    n = d["stratify"]
    blocks = []
    for seed in (1, 2, 3):
        x = traffic.draw_lengths(d, 3 * n, np.random.RandomState(seed))
        for i in range(3):
            blocks.append(sorted(x[i * n:(i + 1) * n]))
    assert all(b == blocks[0] for b in blocks)
    # ... and the block is the distribution's: its median sits at the
    # lognormal's, its ends are clipped
    assert blocks[0][0] >= d["min"] and blocks[0][-1] <= d["max"]
    assert np.median(blocks[0]) == pytest.approx(d["median"], rel=0.05)
    orders = {tuple(traffic.draw_lengths(d, n, np.random.RandomState(s)))
              for s in range(5)}
    assert len(orders) == 5                 # the ORDER is what the seed draws
    # ... once a run: every block repeats it, so the traffic is periodic
    x = traffic.draw_lengths(d, 3 * n + 5, np.random.RandomState(9))
    assert list(x[:n]) == list(x[n:2 * n]) == list(x[2 * n:3 * n])
    assert list(x[3 * n:]) == list(x[:5])


def test_unknown_distributions_and_processes_are_refused():
    rs = np.random.RandomState(0)
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "zipf", "min": 1, "max": 2}, 4, rs)
    with pytest.raises(ValueError):
        traffic.arrival_times({"process": "gamma", "rate_per_s": 1.0, "cv": 2},
                              10.0, rs)
    with pytest.raises(ValueError):
        traffic.arrival_times({"rate_per_s": 0.0}, 10.0, rs)


def test_fixed_count_arrivals_offer_the_same_load_on_every_seed():
    arr = {"process": "poisson", "rate_per_s": 3.0, "fixed_count": True}
    for seed in range(4):
        t = traffic.arrival_times(arr, 40.0, np.random.RandomState(seed))
        assert len(t) == 120 and np.all(np.diff(t) >= 0)
        assert 0.0 <= t[0] and t[-1] < 40.0


def test_poisson_gaps():
    rs = np.random.RandomState(0)
    t = traffic.arrival_times({"process": "poisson", "rate_per_s": 50.0},
                              200.0, rs)
    gaps = np.diff(t)
    assert t[0] == 0.0                      # the schedule starts with work
    assert np.mean(gaps) == pytest.approx(1 / 50.0, rel=0.05)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)


def test_train_batches_are_seeded_and_fresh_every_step():
    m = {"kind": "train", "batch": 2, "seq_len": 64}
    a, b = traffic.train_batch(m, 1000, 3, 0), traffic.train_batch(m, 1000, 3, 0)
    c = traffic.train_batch(m, 1000, 3, 1)
    assert np.array_equal(a["ids"], b["ids"])
    assert not np.array_equal(a["ids"], c["ids"])
    assert a["ids"].shape == (2, 64) and a["ids"].dtype == np.int32
    assert np.array_equal(a["labels"][:, :-1], a["ids"][:, 1:])
    assert np.all(a["labels"][:, -1] == -1)


def test_ttft_runs_from_the_due_time_when_the_submit_is_late():
    Rec = serve_runner.Rec
    win = (100.0, 130.0)
    # due at 101.0, the loop got round to submitting it at 101.4 (a stalled
    # step), first token at 101.9: the user waited 900 ms, not 500
    late = Rec(1, prompt_len=100, max_new=4, due=101.0, submitted=101.4,
               first=101.9, last=102.2, tokens=4, done_at=102.2,
               state="finished", queue_ms=3.0)
    # tokens in bursts: 9 tokens, first at 110.0, last at 110.8 -> 100 ms a
    # token whatever the gaps between callbacks were
    burst = Rec(2, prompt_len=50, max_new=9, due=109.0, submitted=109.0,
                first=110.0, last=110.8, tokens=9, done_at=110.8,
                state="finished", queue_ms=1.0)
    # due before the window: no TTFT sample; finished inside: a TPOT sample
    early = Rec(3, prompt_len=10, max_new=3, due=99.0, submitted=99.0,
                first=99.5, last=100.5, tokens=3, done_at=100.5,
                state="finished", queue_ms=0.0)
    # due in the window, still decoding when it closed: TTFT only
    open_ = Rec(4, prompt_len=10, max_new=50, due=129.0, submitted=129.0,
                first=129.5, last=129.9, tokens=5)
    m = dict(recs={r.rid: r for r in (late, burst, early, open_)}, win=win,
             seconds=30.0, prompt_tokens=160, output_tokens=20,
             open_loop=True)
    s = serve_runner.summarize(m)
    assert s["due"] == 3 and s["finished"] == 3
    assert sorted(s["ttft_ms"]) == pytest.approx([500.0, 900.0, 1000.0])
    assert sorted(s["tpot_ms"]) == pytest.approx([100.0, 100.0, 500.0])
    assert s["served_tokens_per_s"] is None      # an open loop has no cycle
    assert s["served_tokens_per_s_whole_window"] == pytest.approx(6.0)
    assert not s["no_first"] and not s["short"] and not s["bad_state"]
    assert serve_runner.end_to_end_value("ttft_p50_ms", s) == pytest.approx(900.0)
    assert serve_runner.end_to_end_value("tpot_p50_ms", s) == pytest.approx(100.0)
    assert serve_runner.end_to_end_value("ttft_p99_ms", s) is None


def test_served_rate_times_whole_cycles_of_a_periodic_mix():
    # blocks of 2 requests, 5000 tokens a block.  Position 0 gets its first
    # tokens at 1.0, 6.5 and 11.0 s (seq 0, 2, 4): two blocks in 10 s.
    # Position 1 at 4.0 and 9.5 (seq 1, 3): one block in 5.5 s; its third
    # occurrence fell after the window.  Three blocks in 15.5 s — whatever
    # was half prefilled at either end, and in whatever order they landed.
    events = [(1.0, 700, 0), (4.0, 4300, 1), (9.5, 4300, 3), (6.5, 700, 2),
              (11.0, 700, 4)]
    m = dict(seconds=12.0, prompt_tokens=10700, output_tokens=40, block=2,
             block_tokens=5000, first_token_events=events)
    assert serve_runner.served_rate(m) == pytest.approx(3 * 5000 / 15.5)
    # one definition: no cycle, or no position seen twice, is no value
    assert serve_runner.served_rate(dict(m, block=0)) is None
    assert serve_runner.served_rate(dict(
        m, first_token_events=events[:2])) is None


def test_the_docs_mix_is_periodic_in_one_order_whatever_the_seed():
    m = mix("docs-backlog")
    runs = [traffic.serve_requests(m, 32000, seed, 0.0, n_closed=24)
            for seed in (1, 2, 3)]
    pairs = [[(len(r.prompt), r.max_new) for r in reqs] for reqs in runs]
    for p in pairs:
        assert p[:8] == p[8:16] == p[16:24]                 # periodic
        assert sum(a + b for a, b in p[:8]) == 24104        # the same work
    # the mix's order_seed draws the order, the run's seed the token ids
    assert pairs[0] == pairs[1] == pairs[2]
    assert pairs[0][:3] == [(6144, 86), (1503, 161), (1909, 48)]
    assert not np.array_equal(runs[0][0].prompt, runs[1][0].prompt)
    # without the key the order is the seed's again (what the driver's
    # check read 9-10% wide on the chip, PR 22), and order_seed 9 is the
    # order that seed 9 drew then
    seeded = {k: v for k, v in m.items() if k != "order_seed"}
    drawn = [[(len(r.prompt), r.max_new) for r in traffic.serve_requests(
        seeded, 32000, seed, 0.0, n_closed=8)] for seed in (1, 2, 9)]
    assert drawn[0] != drawn[1] and drawn[2] == pairs[0][:8]
    assert serve_runner.Loop._periodic(m, runs[0]) == {
        "block": 8, "block_tokens": 24104}
    assert serve_runner.Loop._periodic(mix("chat-steady"), runs[0])[
        "block"] == 0                                       # an open loop


def test_a_closed_loop_is_not_held_to_first_tokens_for_its_queue():
    Rec = serve_runner.Rec
    queued = Rec(1, 10, 4, due=1.0, submitted=1.0)       # still in the backlog
    s = serve_runner.summarize(dict(
        recs={1: queued}, win=(0.0, 10.0), seconds=10.0, prompt_tokens=0,
        output_tokens=0, open_loop=False))
    assert s["no_first"] == []


def test_a_request_without_a_first_token_or_cut_short_fails():
    Rec = serve_runner.Rec
    win = (0.0, 10.0)
    lost = Rec(1, 10, 4, due=1.0, submitted=1.0)
    short = Rec(2, 10, 4, due=2.0, submitted=2.0, first=2.5, last=3.0,
                tokens=3, done_at=3.0, state="finished")
    failed = Rec(3, 10, 4, due=3.0, submitted=3.0, first=3.5, last=3.5,
                 tokens=1, done_at=3.6, state="failed")
    s = serve_runner.summarize(dict(
        recs={r.rid: r for r in (lost, short, failed)}, win=win, seconds=10.0,
        prompt_tokens=0, output_tokens=0))
    assert s["no_first"] == [1] and s["short"] == [2] and s["bad_state"] == [3]
