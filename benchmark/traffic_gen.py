"""Seeded traffic: every request of a run is made here from the traffic
file's parameters and ``--seed``; the program sees only the inputs.

Every seed does IDENTICAL work: request sizes, shared-prefix flags and
arrival instants, and which size meets which arrival, all come from the
traffic file's own ``sizes_seed``. ``--seed`` draws the token values
(and the weights), nothing else: on the chip, sizes permuted by
``--seed`` changed the work itself (PERF.md Findings, PR 24).

Length distributions (``lengths/<dist>.py``) and arrival processes
(``arrivals/<process>.py``) are found by the name the traffic file
gives, so a new one is a new file and no edit here.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    due_s: float                # open loop: offset from the window's start
    prompt: np.ndarray          # (n,) int32
    max_new_tokens: int
    shared_prefix: int = 0      # leading tokens taken from a system prompt


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lengths from ``lengths/<spec["dist"]>.py``, all within the
    spec's own ``lo..hi``."""
    out = np.asarray(importlib.import_module(
        f"lengths.{spec['dist']}").draw(spec, n, rng))
    if n and (out.min() < spec["lo"] or out.max() > spec["hi"]):
        raise ValueError(f"{spec['dist']} drew a length outside "
                         f"{spec['lo']}..{spec['hi']}")
    return out


def arrival_times(spec: dict, seconds: float, rate: float, rng) -> np.ndarray:
    """Sorted arrival offsets in ``[0, seconds)`` from
    ``arrivals/<spec["process"]>.py``."""
    return importlib.import_module(
        f"arrivals.{spec['process']}").times(spec, seconds, rate, rng)


def _sizes(traffic: dict, n: int, max_total: int):
    """The fixed multiset: (prompt_len, out_len, shared_flag) x n."""
    rng = np.random.default_rng(traffic["sizes_seed"])
    prompts = draw_lengths(traffic["prompt_tokens"], n, rng)
    outs = draw_lengths(traffic["output_tokens"], n, rng)
    share = traffic.get("shared_prefix")
    flags = np.zeros(n, bool)
    if share:
        flags = rng.uniform(size=n) < share["share_of_requests"]
        # a sharing request opens with the system prompt and says
        # something of its own after it
        floor = share["tokens"] + share["min_own_tokens"]
        prompts = np.where(flags, np.maximum(prompts, floor), prompts)
    outs = np.minimum(outs, max_total - prompts)
    if (outs < 1).any():
        raise ValueError("a prompt leaves no room for output")
    return prompts, outs, flags


def make_requests(traffic: dict, n: int, seed: int, vocab: int,
                  max_total: int, due: Optional[np.ndarray] = None
                  ) -> List[Req]:
    """``n`` requests: sizes in the traffic file's own order, tokens
    drawn from ``seed``."""
    prompts, outs, flags = _sizes(traffic, n, max_total)
    rng = np.random.default_rng(seed)
    share = traffic.get("shared_prefix")
    systems = []
    if share:
        systems = [rng.integers(0, vocab, share["tokens"]).astype(np.int32)
                   for _ in range(share["distinct"])]
    reqs = []
    for j in range(n):
        tokens = rng.integers(0, vocab, int(prompts[j])).astype(np.int32)
        shared = 0
        if flags[j]:
            sp = systems[int(rng.integers(0, len(systems)))]
            tokens[:len(sp)] = sp
            shared = len(sp)
        reqs.append(Req(due_s=float(due[j]) if due is not None else 0.0,
                        prompt=tokens, max_new_tokens=int(outs[j]),
                        shared_prefix=shared))
    return reqs


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int,
              max_total: int, rate: Optional[float] = None) -> List[Req]:
    """The whole schedule of an open-loop window, made before it."""
    rate = traffic["rate_per_s"] if rate is None else rate
    rng = np.random.default_rng(traffic["sizes_seed"] + 1)
    due = arrival_times(traffic["arrivals"], seconds, rate, rng)
    return make_requests(traffic, len(due), seed, vocab, max_total, due)


def closed_loop(traffic: dict, seed: int, vocab: int, max_total: int
                ) -> List[List[Req]]:
    """Per client, the requests it sends one after another."""
    clients = traffic["clients"]
    per = traffic["requests_per_client"]
    reqs = make_requests(traffic, clients * per, seed, vocab, max_total)
    return [reqs[c::clients] for c in range(clients)]
