"""The one traffic generator: reads a mix's parameters, draws requests.

A mix file (``bench/traffic/<name>.json``) gives the loop and the length
distributions:

    {"loop": "closed", "streams": 42, "first_prompt_len": 128,
     "warmup_tokens": 44,
     "prompt_len": {"median": 128, "sigma": 0.7, "min": 32, "max": 512},
     "output_len": {"median": 160, "sigma": 0.7, "min": 32, "max": 512}}

    {"loop": "open", "rate_per_s": 2.0, "warmup_s": 10, ...}

A mix may also set the deployment it runs against: ``max_seq_len``
(tokens of a slot, for its longest request) and ``prefill_chunk`` (the
plan's prompt tokens a round); ``cells.Cell`` reads both.

Lengths are lognormal (``median``, log-space ``sigma``), clipped to
``[min, max]``.  Lengths and inter-arrival gaps are drawn at evenly
spaced quantiles into one fixed schedule per mix (see each loop), which
every seed shares; token ids are uniform over the vocabulary and drawn
from the seed.  So a seed changes what each request says, never how much
work a run holds or when it comes.  (A seed that only reordered the same
lengths still moved a 45-second window's readings by a tenth: in a window
that holds about ten new requests, which of them meet in one round sets
the tail and the rate.)
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional

import numpy as np

POOL_PER_STREAM = 64        # closed loop: lengths drawn per stream, per block


@dataclasses.dataclass
class Req:
    idx: int                    # position in the mix: rid = idx
    stream: int
    prompt_len: int
    max_new: int
    arrival_s: Optional[float] = None   # open loop: offset in its phase


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's evenly spaced quantiles."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(rate: float, n: int, span_s: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at evenly spaced quantiles,
    scaled so that they add up to ``span_s``."""
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)]) / rate
    return g * (span_s / g.sum())


def prompt_tokens(seed: int, idx: int, n: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 7, idx])
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)


class ClosedLoop:
    """``streams`` clients, each sending its next request the moment its
    last one finished.

    The work is one fixed table of per-stream request sequences: the j-th
    requests of all streams take the evenly spaced lengths of a pool of
    ``streams`` entries, in a fixed order.

    Each stream's first request stands for one already under way when the
    window opens: its prompt is ``first_prompt_len`` tokens, and the i-th
    of n sequences has (i + 0.5) / n of an output length left when the
    window opens.  The warm-up prefills the first prompts in stream order,
    one a round when ``first_prompt_len`` is the engine's chunk, so stream
    i decodes about ``warmup_tokens`` * (n - i) / n tokens before the
    window: that much is added to its answer, so none finishes during the
    warm-up and completions spread over the window from its start."""

    def __init__(self, mix: Dict):
        self.mix = mix
        self.streams = n = int(mix["streams"])
        self._sent = [0] * n
        self._levels: Dict[int, tuple] = {}
        i = np.arange(n)
        u = (i + 0.5) / n
        full = np.random.default_rng(0).permutation(
            quantile_lengths(mix["output_len"], n))
        ahead = np.ceil(int(mix["warmup_tokens"]) * (n - i) / n)
        self._first_out = np.minimum(
            np.maximum(1, np.ceil(u * full)) + ahead,
            int(mix["output_len"]["max"])).astype(int)

    def _level(self, j: int):
        if j not in self._levels:
            rng = np.random.default_rng([0, j])
            self._levels[j] = (
                rng.permutation(quantile_lengths(self.mix["prompt_len"],
                                                 self.streams)),
                rng.permutation(quantile_lengths(self.mix["output_len"],
                                                 self.streams)))
        return self._levels[j]

    def next(self, stream: int) -> Req:
        j = self._sent[stream]
        self._sent[stream] += 1
        i = stream
        if j == 0:
            return Req(idx=stream, stream=stream,
                       prompt_len=int(self.mix["first_prompt_len"]),
                       max_new=int(self._first_out[i]))
        prompts, outs = self._level(j)
        return Req(idx=stream + j * self.streams, stream=stream,
                   prompt_len=int(prompts[i]), max_new=int(outs[i]))


class OpenLoop:
    """Independent users arriving as a Poisson process at ``rate_per_s``:
    ``warmup_s`` of arrivals before the window, then exactly
    ``round(rate * seconds)`` arrivals spread over the window.  Each phase
    is one fixed schedule of gaps and lengths."""

    def __init__(self, mix: Dict, seconds: float):
        self.mix = mix
        rate = float(mix["rate_per_s"])
        self.phases: Dict[str, List[Req]] = {}
        base = 0
        for k, (tag, span) in enumerate((("warmup", float(mix["warmup_s"])),
                                         ("window", float(seconds)))):
            n = max(1, int(round(rate * span)))
            fixed = np.random.default_rng([0, k])
            gaps = fixed.permutation(quantile_gaps(rate, n, span))
            p = fixed.permutation(quantile_lengths(mix["prompt_len"], n))
            o = fixed.permutation(quantile_lengths(mix["output_len"], n))
            # the first arrival of a phase comes at its start
            at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            self.phases[tag] = [
                Req(idx=base + i, stream=base + i, prompt_len=int(p[i]),
                    max_new=int(o[i]), arrival_s=float(at[i]))
                for i in range(n)]
            base += n
