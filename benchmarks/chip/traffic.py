"""One general generator for every traffic mix file under ``traffic/``.

A mix is data: a JSON object whose ``kind`` picks one of the shapes
below, and whose other keys are its parameters.

The schedule - when each request arrives, its prompt length and its
output length - is a property of the mix: it is drawn from the mix's own
``schedule_seed``, the same for every ``--seed``. The run's seed draws
the prompt tokens (and the weights), so two seeds give the same work at
the same times on other inputs. A window holds some tens of requests, and
a tail over so few swings with their arrangement: drawn per seed, the
p95 TTFT of the qwen3-chat cell on one TPU v5e spread by half its median
from seed to seed.

``lm_open_poisson``: independent users, open loop. Arrivals at a fixed
``rate_rps`` with exponential gaps; prompt lengths drawn from
``prompt_lens`` with probabilities ``prompt_p``; output lengths from
``output`` (``lognormal`` with ``median``/``sigma``, or ``uniform``),
clipped to [``min``, ``max``]. Three phases are laid out one after the
other, each a fixed set of its own: the pre-roll (``preroll_s`` before
the window), the window (``seconds``) and the tail (``tail_s`` after it,
so that requests due late in the window still see the same load).

``cnn_closed_loop``: back-to-back calls of one ``batch``, cycling over
``distinct_batches`` inputs, with about ``ahead_s`` seconds of calls
dispatched ahead of the one waited for.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float              # seconds from the start of the window (<0: pre-roll)
    prompt_len: int
    gen_len: int
    phase: str            # "preroll" | "window" | "tail"


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _gaps(n: int, rate: float) -> np.ndarray:
    """n exponential gaps at their quantiles, summing to n / rate."""
    g = -np.log1p(-_quantiles(n))
    return g / g.sum() * (n / rate)


def _prompts(n: int, lens, probs) -> np.ndarray:
    """n prompt lengths in the exact proportions ``probs`` (largest
    remainders)."""
    raw = np.asarray(probs, float) * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(lens, int), counts)


def _outputs(n: int, spec: dict) -> np.ndarray:
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def lm_schedule(mix: dict, seconds: float) -> list[Arrival]:
    """Every arrival of a run, in time order: sizes at the quantiles of
    their distributions, arranged by the mix's ``schedule_seed``."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    rate = float(mix["rate_rps"])
    out, start = [], -float(mix["preroll_s"])
    for phase, length in (("preroll", mix["preroll_s"]), ("window", seconds),
                          ("tail", mix["tail_s"])):
        n = max(1, int(round(rate * length)))
        gaps = rng.permutation(_gaps(n, n / length))
        lens = rng.permutation(_prompts(n, mix["prompt_lens"],
                                        mix["prompt_p"]))
        gens = rng.permutation(_outputs(n, mix["output"]))
        t = start + np.cumsum(gaps) - gaps     # first one at the start
        out += [Arrival(float(ti), int(p), int(g), phase)
                for ti, p, g in zip(t, lens, gens)]
        start += length
    return out


def prompt_tokens(seed: int, lengths, vocab: int) -> list[np.ndarray]:
    """Token ids of each prompt: uniform over [1, vocab), from the seed."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(1, vocab, size=n, dtype=np.int32) for n in lengths]


# The program's prefill attention works in blocks of this many positions:
# a prompt longer than one block has to fill whole blocks.
PREFILL_BLOCK = 512


def check_fits(mix: dict) -> None:
    """Every request fits the cache (longest prompt + longest output), and
    every prompt length is one the program's prefill takes."""
    need = max(mix["prompt_lens"]) + mix["output"]["max"]
    if need > mix["max_seq"]:
        raise ValueError(f"longest request needs {need} positions, the "
                         f"mix's max_seq is {mix['max_seq']}")
    bad = [n for n in mix["prompt_lens"]
           if n > PREFILL_BLOCK and n % PREFILL_BLOCK]
    if bad:
        raise ValueError(f"prompt lengths {bad} are over {PREFILL_BLOCK} and "
                         f"not a multiple of it")
