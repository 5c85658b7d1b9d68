"""Drive one window of traffic through the program's front door.

Clients are coroutines of the benchmark on the same event loop as
``AsyncServer.run()``, the pump that calls ``Scheduler.step()``.  Each one
submits its request at its due time, and timestamps every token when its
``TokenStream`` yields it.  The benchmark wraps ``Scheduler.step`` on the
instance, without changing the program, to time each step on the host,
note what it computed (for the FLOP and byte counts) and mark it in the
profiler's trace.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import jax

# host spans written into the profiler's trace: idle device time is
# attributed to whichever of these the host was in
STEP_SPAN = "bench.step"
CLIENT_SPAN = "bench.client"
DRAINED_MARK = "bench.drained"  # a step left no request pending
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Sent:
    """One request as a client saw it."""

    planned: object
    due: float = 0.0  # perf_counter time it was due
    sent: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    rows: List[np.ndarray] = dataclasses.field(default_factory=list)
    request: object = None  # the scheduler's Request once the stream ended


class LogitTap:
    """The scheduler's sample function: the scheduler's own greedy sampler,
    keeping the row's logits at a fixed set of vocabulary columns for the
    request whose ``on_token`` hook fires right after (``check`` holds them
    against the reference)."""

    def __init__(self, columns: np.ndarray):
        self.columns = columns
        self.last: Optional[np.ndarray] = None

    def __call__(self, logits: np.ndarray) -> np.ndarray:
        from repro.serving.scheduler import greedy_sample

        self.last = np.asarray(logits[0, self.columns], np.float32)
        return greedy_sample(logits)


@dataclasses.dataclass
class Step:
    """One ``Scheduler.step`` call as the host saw it."""

    t0: float
    t1: float
    occupancy: float
    decode_ctx: List[int]  # attended positions of each decoded token
    prefill: List[tuple]  # (offset, tokens, prompt done) of each advance


class StepLog:
    """Wraps ``sched.step`` and records every call."""

    def __init__(self, sched):
        self.sched = sched
        self.steps: List[Step] = []
        self._step = sched.step
        sched.step = self

    def __call__(self):
        sched = self.sched
        before = {id(s.request): (s.request, s.prefill_pos,
                                  len(s.request.generated))
                  for s in sched.slots if s.live}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            out = self._step()
        t1 = time.perf_counter()
        after = {id(s.request): s for s in sched.slots if s.live}
        reqs = {k: v[0] for k, v in before.items()}
        reqs.update({k: s.request for k, s in after.items()})
        decode, prefill = [], []
        for k, req in reqs.items():
            pos0, gen0 = before[k][1:] if k in before else (0, 0)
            gen1 = len(req.generated)
            if gen0 == 0:  # was admitting: how far did its prompt get?
                slot = after.get(k)
                if slot is not None and slot.state.name == "PREFILLING":
                    pos1 = slot.prefill_pos
                else:
                    pos1 = req.prompt_len if gen1 else pos0
                if pos1 > pos0:
                    prefill.append((pos0, pos1 - pos0,
                                    pos1 >= req.prompt_len))
            # the batched decode step made every new token but a first one,
            # which the prompt's last chunk made; generated[g] attended
            # prompt_len + g positions
            n_dec = gen1 - gen0 - (1 if gen0 == 0 and gen1 else 0)
            decode += [req.prompt_len + g for g in range(gen1 - n_dec, gen1)]
        self.steps.append(Step(t0, t1, sched.occupancy[-1], decode, prefill))
        if not sched.num_pending:
            with jax.profiler.TraceAnnotation(DRAINED_MARK):
                pass
        return out


async def _consume(server, rec: Sent, now: Callable[[], float]) -> None:
    rec.sent = now()
    stream = server.submit(rec.planned.prompt, rec.planned.max_new)
    tap, req = server.sched.sample_fn, server.sched.queue[-1]
    if isinstance(tap, LogitTap) and req.rid == stream.rid:
        hook = req.on_token

        def on_token(r, tok):
            rec.rows.append(tap.last)
            hook(r, tok)

        req.on_token = on_token
    async for tok in stream:
        with jax.profiler.TraceAnnotation(CLIENT_SPAN):
            rec.times.append(now())
            rec.tokens.append(tok)
    rec.request = stream.request


async def _open_client(server, rec: Sent, now) -> None:
    delay = rec.due - now()
    if delay > 0:
        await asyncio.sleep(delay)
    await _consume(server, rec, now)


def run_traffic(sched, planned, warm_s: float, seconds: float,
                marks=()) -> Dict:
    """Serve ``planned`` through an ``AsyncServer`` over ``sched``: a warm
    period of ``warm_s`` seconds, then the window of ``seconds``.  Returns
    the client records, the window's bounds and the step log.  ``marks``
    are ``(seconds after the window opens, fn)`` calls made between steps
    (the traced run starts and stops the profiler with them)."""
    from repro.serving.server import AsyncServer

    now = time.perf_counter
    log = StepLog(sched)
    recs = [Sent(planned=p) for p in planned]

    async def main():
        server = AsyncServer(sched)
        pump = asyncio.create_task(server.run())
        start = now()
        t_open = start + warm_s
        t_close = t_open + seconds
        for r in recs:
            r.due = start + r.planned.due_s
        tasks = [asyncio.create_task(_open_client(server, r, now))
                 for r in recs if r.due < t_close]
        loop = asyncio.get_running_loop()
        for offset, fn in marks:
            loop.call_at(loop.time() + (t_open + offset - now()), fn)
        await asyncio.sleep(max(0.0, t_open - now()))
        opened = now()
        await asyncio.sleep(max(0.0, t_close - now()))
        closed = now()
        server.close()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await pump
        return opened, closed, t_open, t_close

    opened, closed, t_open, t_close = asyncio.run(main())
    return {"records": recs, "open": opened, "close": closed,
            "due_open": t_open, "due_close": t_close,
            "steps": log.steps, "start": opened - warm_s}


def summarize(result: Dict) -> Dict:
    """End-to-end numbers of one window, from the clients' timestamps.
    Tokens count between the window's measured bounds; the requests due in
    it are those planned for it, whenever a busy loop let it open."""
    t_open, t_close = result["open"], result["close"]
    seconds = t_close - t_open
    recs = result["records"]
    toks = sum(1 for r in recs for t in r.times if t_open <= t < t_close)
    gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
            if t_open <= a and b < t_close]
    due = _due(result)
    failed = [r for r in due if r.request is not None and (
        r.request.cancelled and r.request.cancel_time < t_close)]
    return {
        "seconds": seconds,
        "tokens": toks,
        "tokens_per_s": toks / seconds,
        "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3 if gaps else None,
        "ttft_p90_s": ttft_p90_s(result),
        "sender_lag_p99_ms": sender_lag_p99_ms(result),
        "attempted": len(due),
        "failed": len(failed),
        "gaps": len(gaps),
    }


def _due(result: Dict, skip=(0.0, 0.0)):
    """The requests planned for the window but those due in ``skip`` (a
    traced run's profiler start, which holds the loop itself)."""
    return [r for r in result["records"]
            if result["due_open"] <= r.due < result["due_close"]
            and not skip[0] <= r.due < skip[1]]


def ttft_p90_s(result: Dict, skip=(0.0, 0.0)):
    """90th percentile of the time from due to the first token at the
    client; a request with none by the close counts at the close."""
    t_close = result["close"]
    ttft = [min(r.times[0], t_close) - r.due if r.times else t_close - r.due
            for r in _due(result, skip)]
    return float(np.percentile(ttft, 90)) if ttft else None


def sender_lag_p99_ms(result: Dict, skip=(0.0, 0.0)):
    """How late the 99th-percentile client sent its request after it was
    due."""
    lag = [r.sent - r.due for r in _due(result, skip) if r.sent is not None]
    return float(np.percentile(lag, 99)) * 1e3 if lag else None
