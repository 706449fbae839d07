"""The one traffic generator. A mix is a JSON file of parameters
(``bench/traffic/<mix>.json``); a cell adds its rate (``bench/cells``).

Two loop kinds:

  open     requests arrive on a schedule (Poisson at the cell's rate),
           whether or not earlier ones have finished.
  offline  a backlog is kept topped up so that no slot waits for work;
           the first wave starts part-way through its requests (below).

Prompt and output lengths are each a clipped lognormal ``{"dist":
"lognormal", "median", "sigma", "min", "max"}``; open-loop arrivals are
``"poisson"``. A mix that needs another shape (bursts, shared prefixes,
short and long requests in one queue) comes with the branch that reads it.

Every seed gets the same set of sizes and arrival gaps, in another order:
the sizes and gaps are drawn once from the mix's own ``size_seed``, and the
run's seed permutes them and draws the token ids. So two seeds do the same
work and differ in which request comes when; the spread between seeds is
the system's, not the draw's.

Offline first wave: a slot in steady state holds a request part-way
through its output. Its output length is length-biased (a long request is
more likely to be in flight) and its age uniform within it, so each
first-wave request takes an output length drawn with probability
proportional to the length, is given a uniform share of it as already
generated (extra prompt tokens) and asks for the rest. Set-up fills the
slots with that wave and ends when each has its first token.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Req:
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due_s: float | None = None  # seconds after the window opens (open loop)


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(mix: dict, rate: float, n: int, rng) -> np.ndarray:
    arr = mix.get("arrivals", "poisson")
    if arr != "poisson":
        raise ValueError(f"unknown arrivals {arr!r}")
    return rng.exponential(1.0 / rate, n)


def _prompts(lengths, rng, vocab: int) -> list:
    return [_tokens(rng, int(n), vocab) for n in lengths]


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, n, dtype=np.int64).astype(np.int32)


def _run_rng(seed: int, stream: int):
    return np.random.default_rng([stream, int(seed)])


def open_requests(mix: dict, rate: float, seconds: float, seed: int,
                  vocab: int) -> list[Req]:
    """Every request due in a window of ``seconds`` at ``rate`` per second,
    sorted by due time. The count is ``round(rate * seconds)`` for every
    seed: the drawn gaps are scaled so the last one is due before the
    window closes."""
    n = max(1, int(round(rate * seconds)))
    size_rng = np.random.default_rng(mix["size_seed"])
    prompt = _lengths(mix["prompt_tokens"], n, size_rng)
    out = _lengths(mix["output_tokens"], n, size_rng)
    gaps = _gaps(mix, rate, n, size_rng)
    gaps *= seconds * (1 - 0.5 / n) / gaps.sum()
    rng = _run_rng(seed, 0)
    prompt, out = rng.permutation(prompt), rng.permutation(out)
    due = np.cumsum(rng.permutation(gaps))
    prompts = _prompts(prompt, rng, vocab)
    return [Req(p, int(o), float(t)) for p, o, t in zip(prompts, out, due)]


def offline_requests(mix: dict, slots: int, seed: int, vocab: int,
                     count: int) -> tuple[list[Req], list[Req]]:
    """(first wave of ``slots`` requests, ``count`` backlog requests)."""
    size_rng = np.random.default_rng(mix["size_seed"])
    # first wave: length-biased total output, uniform age within it
    pool = _lengths(mix["output_tokens"], 64 * slots, size_rng)
    total = size_rng.choice(pool, slots, p=pool / pool.sum())
    age = np.floor(size_rng.uniform(0, 1, slots) * total).astype(np.int64)
    wave_prompt = _lengths(mix["prompt_tokens"], slots, size_rng) + age
    wave_out = total - age
    prompt = _lengths(mix["prompt_tokens"], count, size_rng)
    out = _lengths(mix["output_tokens"], count, size_rng)
    rng = _run_rng(seed, 1)
    order = rng.permutation(slots)
    wave = [Req(p, int(o)) for p, o in zip(
        _prompts(wave_prompt[order], rng, vocab),
        wave_out[order])]
    prompt, out = rng.permutation(prompt), rng.permutation(out)
    backlog = [Req(p, int(o)) for p, o in zip(
        _prompts(prompt, rng, vocab), out)]
    return wave, backlog
