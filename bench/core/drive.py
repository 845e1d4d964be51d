"""The load loop: offers a cell's traffic to the engine and records, on
the host clock, when each request arrived, left the queue, received its
first and last token and finished, and what each scheduler tick did.

One thread drives everything.  Token times are the moments ``step()``
returns them to the host; tokens arrive in chunks of the engine's
``decode_chunk``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.serving import steps as serving_steps


@dataclasses.dataclass
class Req:
    i: int  # index in the traffic stream
    obj: object  # the engine's Request
    n_prompt: int
    n_out: int
    arrival: float  # due time (open loop) or send time (closed loop)
    submitted: float
    client: int = -1
    slot: int = -1  # the engine slot it decoded in, as observed
    admitted: Optional[float] = None  # observed leaving the queue
    first: Optional[float] = None
    last: Optional[float] = None
    done: Optional[float] = None
    seen: int = 0
    plan: Optional[List[Tuple[int, int]]] = None
    chunks_done: int = 0


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    decode_ctx: List[int]  # context of each decode query token
    prefill_ctx: List[int]  # context of each real prompt token prefilled
    head_tokens: int  # tokens whose logits were needed
    tokens: int  # output tokens received


class LoadLoop:
    def __init__(self, eng, traffic, *, clock: Callable[[], float] =
                 time.perf_counter):
        self.eng = eng
        self.traffic = traffic
        self.clock = clock
        self.reqs: List[Req] = []
        self.ticks: List[Tick] = []
        self.failed = 0
        self._next = 0
        self._inflight: List[Req] = []
        self._queued: List[Req] = []
        self._prefilling: List[Req] = []
        self._free: List[Tuple[float, int]] = []  # closed loop (time, client)
        self.t0 = 0.0

    # -- submission ----------------------------------------------------------
    def _submit(self, arrival: float, client: int = -1) -> None:
        i = self._next
        self._next += 1
        prompt, n_out = self.traffic.request(i)
        with TraceAnnotation("submit"):
            try:
                self.eng.submit(prompt, n_out)
            except ValueError:
                self.failed += 1
                return
        r = Req(i, self.eng.queue[-1], len(prompt), n_out, arrival,
                self.clock(), client)
        self.reqs.append(r)
        self._queued.append(r)
        self._inflight.append(r)

    def _submit_due(self, now: float) -> None:
        if self.traffic.loop == "open":
            while self.t0 + self.traffic.arrival(self._next) <= now:
                self._submit(self.t0 + self.traffic.arrival(self._next))
        else:
            while self._free:
                t, c = self._free.pop(0)
                self._submit(t, c)

    # -- observation ---------------------------------------------------------
    def _observe(self, t0: float, t1: float, chunk_ran: bool) -> None:
        eng = self.eng
        if self._queued:
            still = {id(r) for r in eng.queue}
            for r in [r for r in self._queued if id(r.obj) not in still]:
                r.admitted = t1
                r.plan = serving_steps.plan_chunks(r.n_prompt,
                                                   eng.prefill_buckets)
                self._prefilling.append(r)
            self._queued = [r for r in self._queued if id(r.obj) in still]
        prefill_ctx, head = [], 0
        if chunk_ran and self._prefilling:
            r = self._prefilling[0]
            s0, w = r.plan[r.chunks_done]
            real = min(s0 + w, r.n_prompt) - s0
            prefill_ctx = list(range(s0 + 1, s0 + real + 1))
            r.chunks_done += 1
            if r.chunks_done == len(r.plan):
                head += 1
                self._prefilling.pop(0)
        decode_ctx: List[int] = []
        received = 0
        keep = []
        slot_of = {id(q): s for s, q in enumerate(eng.active) if q is not None}
        for r in self._inflight:
            if r.slot < 0:
                r.slot = slot_of.get(id(r.obj), -1)
            n = len(r.obj.output)
            if n > r.seen:
                if r.seen == 0:
                    r.first = t1
                decode_ctx.extend(r.n_prompt + k
                                  for k in range(max(r.seen, 1), n))
                r.last = t1
                received += n - r.seen
                r.seen = n
            if n >= r.n_out:
                r.done = t1
                if self.traffic.loop == "closed":
                    self._free.append((t1, r.client))
            else:
                keep.append(r)
        self._inflight = keep
        self.ticks.append(Tick(t0, t1, decode_ctx, prefill_ctx,
                               head + len(decode_ctx), received))

    # -- the loop ------------------------------------------------------------
    def start(self) -> None:
        """Start the traffic: the open loop's clock, or every closed-loop
        client sending its first request now."""
        self.t0 = self.clock()
        if self.traffic.loop == "closed":
            self._free = [(self.t0, c) for c in range(self.traffic.clients)]

    def run_until(self, t_end: float,
                  on_tick: Optional[Callable[[int], None]] = None) -> None:
        """Drive the engine until the host clock reaches ``t_end``.
        ``on_tick(n)`` is called before each tick with the tick count."""
        eng = self.eng
        while True:
            now = self.clock()
            if now >= t_end:
                return
            self._submit_due(now)
            if eng.idle:
                due = self.t0 + self.traffic.arrival(self._next)
                with TraceAnnotation("wait_arrival"):
                    time.sleep(max(0.0, min(due, t_end) - self.clock()))
                continue
            if on_tick is not None:
                on_tick(len(self.ticks))
            chunks = eng.prefill_chunk_ticks
            t0 = self.clock()
            with TraceAnnotation("tick"):
                eng.step()
            t1 = self.clock()
            self._observe(t0, t1, eng.prefill_chunk_ticks != chunks)

    def lateness(self) -> Dict[str, float]:
        """How late the generator submitted, against each due time."""
        late = [r.submitted - r.arrival for r in self.reqs]
        return {"max_s": max(late, default=0.0),
                "mean_s": sum(late) / len(late) if late else 0.0}
