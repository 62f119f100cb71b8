"""Drive the stack: compile warm-up, traffic warm-up, the measured window.

Everything runs on ``time.perf_counter``, the clock the program's tracer
uses, so the harness's own spans and the engine's spans line up.  Every
call into the program (``submit``, ``supervisor.step``) sits in a
``jax.profiler.TraceAnnotation``, so a profiled window can attribute the
device's idle gaps to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from .traffic import ClosedLoop, OpenLoop, Req, prompt_tokens

WARM_RID = 1 << 30          # rid of the compile warm-up request (a uint32)
WARM_LIMIT_S = 600.0        # give up on a warm-up that makes no end
SETTLE_LIMIT_S = 60.0       # after the window: wait this long at most for
#                             the first tokens of the window's requests


@dataclasses.dataclass
class ReqRecord:
    rid: int
    stream: int
    prompt_len: int
    max_new: int
    due: float                  # when it was due to be sent (perf_counter)
    submit: float               # when it was submitted
    prompt: np.ndarray = None


class Driver:
    def __init__(self, stack, mix: Dict, seed: int, vocab: int,
                 rid_base: int = 0):
        self.stack, self.mix, self.seed, self.vocab = stack, mix, seed, vocab
        self.rid_base = rid_base            # keeps drivers of one stack apart
        self.reqs: Dict[int, ReqRecord] = {}
        self.steps: List[tuple] = []        # (t0, t1) of supervisor.step
        self.results: List = []             # (arrival time, result)
        self._seen = 0
        self.origin = time.perf_counter()
        self.window = (0.0, 0.0)
        self.profile_window: Optional[tuple] = None
        self.in_window = False
        self.queue = (0, 0)                 # waiting requests: window start, end
        self.waits: List[tuple] = []        # (t, requests, prompt tokens)
        #                                     waiting for a first token,
        #                                     after each round of the window
        self.settled = 0.0                  # when ``settle`` stopped
        self.gc_pauses: List[tuple] = []    # (start, seconds, generation)
        self._gc_t0 = 0.0

    # -- the two calls into the program ---------------------------------
    def submit(self, rid: int, stream: int, prompt: np.ndarray,
               max_new: int, due: float) -> None:
        from repro.serving.engine import GenerationRequest
        t = time.perf_counter()
        self.reqs[rid] = ReqRecord(rid, stream, len(prompt), max_new, due,
                                   t, prompt)
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.stack.supervisor.submit(
                self.stack.arch,
                GenerationRequest(rid=rid, tokens=prompt,
                                  max_new_tokens=max_new, stream=stream + 1),
                at_server=0, now=t - self.origin)

    def step(self) -> List:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.stack.supervisor.step(t0 - self.origin)
        t1 = time.perf_counter()
        self.steps.append((t0, t1))
        new = self.stack.supervisor.report.results[self._seen:]
        self._seen += len(new)
        for r in new:
            self.results.append((t1, r))
        return new

    def waiting(self):
        """Requests that have not had their first token (queued for a slot,
        or prefilling in one), and the prompt tokens the prefilling ones
        have left."""
        rt = self.stack.runtime
        slots = [s for g in rt.groups.values() for s in g.slots
                 if s.prefilling]
        return (rt.pending() + len(slots),
                sum(len(s.req.tokens) - s.consumed for s in slots))

    def busy(self) -> bool:
        rt = self.stack.runtime
        return bool(rt.pending() or rt.in_flight())

    # -- phases ---------------------------------------------------------
    def compile_warmup(self, buckets, max_seq_len: int) -> None:
        """One request whose prompt takes every chunk bucket once (the
        chunk picker takes the largest bucket that fits, so a prompt of
        the buckets' sum visits each), then a decode step at the arena's
        full width: every shape this cell's traffic reaches."""
        n = min(sum(buckets), max_seq_len - 2)
        self.submit(WARM_RID, -1, prompt_tokens(self.seed, WARM_RID, n,
                                                self.vocab), 2,
                    time.perf_counter())
        self._drain(lambda: not self.busy())

    def _drain(self, done) -> None:
        t0 = time.perf_counter()
        while not done():
            if time.perf_counter() - t0 > WARM_LIMIT_S:
                raise RuntimeError("warm-up made no end")
            self.step()

    def _tokens(self, r: Req) -> np.ndarray:
        return prompt_tokens(self.seed, r.idx, r.prompt_len, self.vocab)

    def run_closed(self, seconds: float, first_token_seen, profile) -> None:
        """``first_token_seen(rids)``: whether each of ``rids`` has had its
        first token.  The window opens once every request sent so far has,
        so no prompt of the warm-up is still waiting for the chunk budget
        when the window's requests come."""
        loop = ClosedLoop(self.mix)
        stream_of = {}

        def send(s: int, due: float) -> None:
            r = loop.next(s)
            stream_of[self.rid_base + r.idx] = s
            self.submit(self.rid_base + r.idx, s, self._tokens(r), r.max_new,
                        due)

        now = time.perf_counter()
        for s in range(loop.streams):
            send(s, now)

        def handle(results, t_end) -> None:
            for res in results:
                s = stream_of.get(res.rid)
                if s is not None and res.sample == 0 and \
                        time.perf_counter() < t_end:
                    send(s, time.perf_counter())

        t0 = time.perf_counter()
        while not first_token_seen(set(self.reqs) - {WARM_RID}):
            if time.perf_counter() - t0 > WARM_LIMIT_S:
                raise RuntimeError("closed-loop warm-up made no end")
            handle(self.step(), float("inf"))
        self._window(seconds, lambda t_end: handle(self.step(), t_end),
                     profile)

    def _open_round(self, reqs: List[Req], state: List[int], start: float,
                    end: float) -> None:
        """Submit the arrivals now due, then step, or sleep until the next
        arrival when the server has nothing to do."""
        now = time.perf_counter()
        while state[0] < len(reqs) and start + reqs[state[0]].arrival_s <= now:
            r = reqs[state[0]]
            self.submit(self.rid_base + r.idx, r.stream, self._tokens(r),
                        r.max_new, start + r.arrival_s)
            state[0] += 1
        if self.busy():
            self.step()
            return
        nxt = start + reqs[state[0]].arrival_s if state[0] < len(reqs) \
            else end
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))

    def run_open(self, seconds: float, profile) -> None:
        loop = OpenLoop(self.mix, seconds)
        t_warm = time.perf_counter()
        warm_end = t_warm + float(self.mix["warmup_s"])
        state = [0]
        while time.perf_counter() < warm_end:
            self._open_round(loop.phases["warmup"], state, t_warm, warm_end)
        state = [0]
        reqs = loop.phases["window"]
        self._window(seconds, lambda t_end: self._open_round(
            reqs, state, self.window[0], t_end), profile)
        # an arrival due in the window's last round is the window's all the
        # same: it is sent now, and its first token counts from when it was
        # due, so every run holds the same requests whatever its timing
        for r in reqs[state[0]:]:
            self.submit(self.rid_base + r.idx, r.stream, self._tokens(r),
                        r.max_new, self.window[0] + r.arrival_s)

    def settle(self, first_token_seen) -> None:
        """After the window, with nothing more sent: step until every
        request due in the window has had its first token (its time to
        first token counts the wait), ``SETTLE_LIMIT_S`` at most."""
        w0, w1 = self.window
        due = {r.rid for r in self.reqs.values() if w0 <= r.due <= w1}
        t0 = time.perf_counter()
        while not first_token_seen(due) and self.busy() and \
                time.perf_counter() - t0 < SETTLE_LIMIT_S:
            self.step()
        self.settled = time.perf_counter()

    def _gc_pause(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.in_window:
            self.gc_pauses.append((self._gc_t0,
                                   time.perf_counter() - self._gc_t0,
                                   info["generation"]))

    def _window(self, seconds: float, one_round, profile) -> None:
        """Run rounds for ``seconds``; ``profile`` (start, stop) brackets
        the last ``profile.seconds`` of them when tracing.  What set-up
        left (weights' host objects, the tracer's warm-up events) is frozen
        out of the collector's scans for the window, and each collection
        inside the window is recorded."""
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._gc_pause)
        try:
            self._rounds(seconds, one_round, profile)
        finally:
            gc.callbacks.remove(self._gc_pause)
            gc.unfreeze()

    def _rounds(self, seconds: float, one_round, profile) -> None:
        w0 = time.perf_counter()
        t_end = w0 + seconds
        self.window = (w0, t_end)
        q0 = self.stack.runtime.pending()
        self.in_window = True
        started = False
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if profile is not None and not started and \
                    now >= t_end - profile.seconds:
                profile.start()
                started = True
                self.profile_window = (time.perf_counter(), None)
            one_round(t_end)
            self.waits.append((time.perf_counter(), *self.waiting()))
        self.in_window = False
        self.queue = (q0, self.stack.runtime.pending())
        if started:
            p1 = time.perf_counter()
            self.profile_window = (self.profile_window[0], p1)
