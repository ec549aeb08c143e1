"""The seeded generators: same seed, same schedule; every seed the same
sizes at the same instants; clips; the share of shared prefixes; the
arrival processes and length distributions found by name."""

import json
import os

import numpy as np

import traffic_gen as tg
from conftest import BENCH


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _key(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens) for r in reqs]


def test_open_loop_same_seed_same_schedule():
    t = _traffic("serve_chat_prompt_heavy")
    a = tg.open_loop(t, 3000000019, 20.0, 50257, 1024, rate=30.0)
    b = tg.open_loop(t, 3000000019, 20.0, 50257, 1024, rate=30.0)
    assert _key(a) == _key(b)
    assert len(a) == 600
    due = [r.due_s for r in a]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 20.0


def test_every_seed_does_identical_work():
    t = _traffic("serve_chat_prompt_heavy")
    a = tg.open_loop(t, 1, 20.0, 50257, 1024, rate=30.0)
    b = tg.open_loop(t, 2, 20.0, 50257, 1024, rate=30.0)
    sizes = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens,
                         r.shared_prefix) for r in rs]
    assert sizes(a) == sizes(b)         # the same size at the same instant
    assert _key(a) != _key(b)           # other tokens


def test_chat_lengths_clipped_and_shared_share():
    t = _traffic("serve_chat_prompt_heavy")
    reqs = tg.open_loop(t, 5, 50.0, 50257, 1024, rate=40.0)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert p.min() >= 64 and p.max() <= 896
    assert o.min() >= 8 and o.max() <= 128
    assert (p + o).max() <= 1024
    assert 330 < np.median(p) < 450 and 38 < np.median(o) < 60
    shared = [r for r in reqs if r.shared_prefix]
    assert 0.35 < len(shared) / len(reqs) < 0.45
    assert all(r.shared_prefix == 256 and len(r.prompt) >= 288
               for r in shared)
    # four distinct system prompts, reused
    heads = {r.prompt[:256].tobytes() for r in shared}
    assert len(heads) == 4


def test_closed_loop_queues():
    t = _traffic("serve_decode_backlog")
    qs = tg.closed_loop(t, 9, 50257, 1024)
    assert len(qs) == t["clients"]
    assert all(len(q) == t["requests_per_client"] for q in qs)
    flat = [r for q in qs for r in q]
    assert all(32 <= len(r.prompt) <= 128 for r in flat)
    assert all(256 <= r.max_new_tokens <= 512 for r in flat)
    assert not any(r.shared_prefix for r in flat)
    assert _key(flat) == _key([r for q in tg.closed_loop(t, 9, 50257, 1024)
                               for r in q])
    # another seed: other tokens, the same sizes in the same order for
    # every client
    other = [r for q in tg.closed_loop(t, 10, 50257, 1024) for r in q]
    assert [(len(r.prompt), r.max_new_tokens) for r in other] == \
        [(len(r.prompt), r.max_new_tokens) for r in flat]
    assert _key(other) != _key(flat)


def test_gamma_arrivals_are_bursty_and_keep_the_count():
    rng = np.random.default_rng(0)
    t = tg.arrival_times({"process": "gamma", "cv": 3.0}, 50.0, 40.0, rng)
    assert len(t) == 2000 and (np.diff(t) >= 0).all()
    assert 0.0 <= t[0] and t[-1] < 50.0
    gaps = np.diff(t)
    assert 2.3 < gaps.std() / gaps.mean() < 3.7
    p = tg.arrival_times({"process": "poisson"}, 50.0, 40.0,
                         np.random.default_rng(0))
    assert 0.9 < np.diff(p).std() / np.diff(p).mean() < 1.1


def test_mixture_lengths():
    spec = {"dist": "mixture", "lo": 8, "hi": 768, "parts": [
        {"weight": 0.9, "dist": "lognormal", "median": 48, "sigma": 0.7,
         "lo": 8, "hi": 128},
        {"weight": 0.1, "dist": "uniform", "lo": 512, "hi": 768}]}
    x = tg.draw_lengths(spec, 4000, np.random.default_rng(1))
    assert x.min() >= 8 and x.max() <= 768
    assert 0.07 < (x >= 512).mean() < 0.13
    assert not ((x > 128) & (x < 512)).any()


def test_unknown_names_are_errors():
    import pytest
    with pytest.raises(ModuleNotFoundError):
        tg.draw_lengths({"dist": "zipf", "lo": 1, "hi": 2}, 3,
                        np.random.default_rng(0))
    with pytest.raises(ModuleNotFoundError):
        tg.arrival_times({"process": "weibull"}, 1.0, 1.0,
                         np.random.default_rng(0))
