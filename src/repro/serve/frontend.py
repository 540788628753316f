"""Async open-loop serving frontend over the continuous-batching engine.

``ServeEngine`` (``serve.scheduler``) is a closed-loop host loop: callers
submit, then spin ``step()`` until done. Open-loop traffic — requests
arriving on their own clock, clients disconnecting, queues overflowing —
needs a frontend that keeps the device busy *while* the host talks to
clients. :class:`AsyncServeFrontend` is that layer, built on three seams
PR 9 added to the engine:

* **Double-buffered step submission** — the engine's ``step_begin``
  dispatches the fused device step asynchronously (JAX dispatch returns
  before the computation finishes) and ``step_commit`` reads it back.
  The frontend runs both halves on a dedicated single-thread executor
  (the engine stays single-threaded by construction) and uses the
  in-flight span to do host-side work: drain the client command queue
  (submits run the radix prefix match + admission planning), push
  streamed tokens to per-request consumers, and let the asyncio event
  loop serve HTTP clients. Host scheduling overlaps device compute
  instead of serializing after it.
* **Bounded admission with explicit shedding** — ``submit`` routes
  through ``ServeEngine.try_submit``: a request arriving at a full
  queue (``SchedulerConfig.max_queue``) resolves immediately with a
  :class:`ShedError` carrying the engine's reason — the
  ``gating_reasons`` honesty idiom applied to load; nothing is silently
  dropped and nothing hangs. Deadlines (``Request.ttft_deadline`` /
  ``Request.deadline``) are enforced by the engine at step boundaries.
* **Step-boundary cancellation** — ``cancel`` marks are applied by the
  engine itself, which defers any cancel arriving mid-flight to the
  commit boundary (the cancel-vs-rewind ordering contract,
  ``serve.kv_pool``). The frontend never touches engine state from the
  event-loop thread while a step is in flight except through the
  engine's own deferral machinery.

Per-request consumption is a :class:`RequestHandle`: ``stream()`` yields
tokens as the engine emits them (an ``asyncio.Queue`` fed from the
engine's event log after every commit) and ``result()`` awaits the
terminal state — one of ``finished / cancelled / timed_out / errored``
with the (possibly partial) output and the engine's explicit reason.

Pure stdlib (asyncio + one worker thread); no HTTP here — the hand-rolled
HTTP/1.1 front door lives in ``launch.serve`` (``--serve``).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
from typing import AsyncIterator, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve.scheduler import Request, ServeEngine


class ShedError(RuntimeError):
    """Raised to a submitter whose request was shed at admission —
    carries the engine's explicit reason (queue full / can-never-fit).
    Explicit rejection is the open-loop backpressure signal; a client
    that sees it can retry, downsize, or go elsewhere."""


@dataclasses.dataclass
class RequestResult:
    """Terminal state of one request as the frontend observed it.

    ``status`` is the engine's lifecycle terminal (``finished``,
    ``cancelled``, ``timed_out``, ``errored``); ``tokens`` the full or
    partial output; ``reason`` the engine's explanation for any
    non-finished terminal; timing fields are seconds from the engine's
    ``submit_time`` stamp, on its clock (``ttft`` to the first token,
    None when no token was ever sampled; ``latency`` to the terminal
    state)."""

    uid: int
    status: str
    tokens: np.ndarray
    reason: Optional[str] = None
    ttft: Optional[float] = None
    latency: float = 0.0


class RequestHandle:
    """Caller-side view of one in-flight request."""

    _DONE = object()                   # stream sentinel

    def __init__(self, uid: int, loop: asyncio.AbstractEventLoop):
        """Created by :meth:`AsyncServeFrontend.submit` only."""
        self.uid = uid
        self._tokens: asyncio.Queue = asyncio.Queue()
        self._result: asyncio.Future = loop.create_future()

    async def stream(self) -> AsyncIterator[int]:
        """Yield tokens as they decode; ends at the terminal state."""
        while True:
            t = await self._tokens.get()
            if t is RequestHandle._DONE:
                return
            yield t

    async def result(self) -> RequestResult:
        """Await the request's terminal state (never raises on timeout/
        cancel/error — the status field reports them; honest outcomes
        beat exceptions for accounting)."""
        return await self._result


class AsyncServeFrontend:
    """Open-loop asyncio frontend driving one :class:`ServeEngine`.

    Usage::

        fe = AsyncServeFrontend(engine)
        await fe.start()
        h = await fe.submit(Request(uid=1, prompt=..., deadline=2.0))
        async for tok in h.stream(): ...
        res = await h.result()           # RequestResult
        await fe.stop()

    ``idle_sleep`` bounds the poll interval while the engine has no
    work; under load the loop is driven by step completion, not the
    timer.
    """

    def __init__(self, engine: ServeEngine, *, idle_sleep: float = 0.002):
        """Wrap ``engine``; call :meth:`start` before submitting."""
        self.engine = engine
        self.idle_sleep = idle_sleep
        self._handles: dict[int, RequestHandle] = {}
        # the engine is not thread-safe: every engine call runs on this
        # one worker thread, serialized by the loop below
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="serve-engine")
        self._commands: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        self._stopping = False
        self.steps = 0

    async def start(self) -> None:
        """Spawn the serving loop task."""
        if self._task is not None:
            raise RuntimeError("frontend already started")
        self._stopping = False
        self._task = asyncio.get_running_loop().create_task(self._loop())

    async def stop(self) -> None:
        """Drain in-flight work and stop the loop task."""
        self._stopping = True
        if self._task is not None:
            await self._task
            self._task = None
        self._exec.shutdown(wait=True)

    async def submit(self, req: Request) -> RequestHandle:
        """Submit with admission control: returns a handle, or raises
        :class:`ShedError` with the engine's explicit reason."""
        loop = asyncio.get_running_loop()
        handle = RequestHandle(req.uid, loop)
        fut: asyncio.Future = loop.create_future()
        await self._commands.put(("submit", req, handle, fut))
        reason = await fut
        if reason is not None:
            raise ShedError(f"request {req.uid} shed: {reason}")
        return handle

    async def cancel(self, uid: int) -> bool:
        """Request cancellation of ``uid``; applied by the engine at the
        next step boundary. True when the request was still live."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        await self._commands.put(("cancel", uid, None, fut))
        return await fut

    # ------------------------------------------------------------------
    # the serving loop
    # ------------------------------------------------------------------

    def _apply_commands(self) -> None:
        """Drain queued client commands into the engine (runs on the
        event-loop thread; engine queue/cancel mutations are host-side
        dicts the in-flight device step never reads, and slot-touching
        cancels are deferred by the engine itself while a step is in
        flight)."""
        with TraceAnnotation("frontend.commands"):
            while True:
                try:
                    kind, arg, handle, fut = self._commands.get_nowait()
                except asyncio.QueueEmpty:
                    return
                if kind == "submit":
                    reason = self.engine.try_submit(arg)
                    if reason is None:
                        self._handles[arg.uid] = handle
                    fut.set_result(reason)
                else:                              # cancel
                    fut.set_result(self.engine.cancel(arg))

    def _pump_events(self) -> None:
        """Move the engine's stream events into per-request queues and
        resolve terminal futures."""
        with TraceAnnotation("frontend.events"):
            eng = self.engine
            for ev in eng.drain_events():
                kind, uid, payload = ev
                h = self._handles.get(uid)
                if h is None:
                    continue
                if kind == "token":
                    h._tokens.put_nowait(int(payload))
                    continue
                # terminal: build the result record
                sub = eng.submit_time[uid]
                first = eng.first_token_at.get(uid)
                res = RequestResult(
                    uid=uid, status=payload,
                    tokens=eng.results.get(uid, np.zeros(0, np.int32)),
                    reason=eng.errors.get(uid),
                    ttft=None if first is None else first - sub,
                    latency=eng.finished_at[uid] - sub)
                h._tokens.put_nowait(RequestHandle._DONE)
                if not h._result.done():
                    h._result.set_result(res)
                del self._handles[uid]

    async def _loop(self) -> None:
        """Serve until :meth:`stop`: overlap host work with the
        in-flight device step (see module docstring)."""
        loop = asyncio.get_running_loop()
        eng = self.engine
        while True:
            self._apply_commands()
            # dispatch on the engine thread — admission (radix match,
            # allocator, admit jit) + async device dispatch
            pending = await loop.run_in_executor(self._exec,
                                                 eng.step_begin)
            if pending is None:
                self._pump_events()    # deadline/shed terminals, faults
                if self._stopping and not self._handles:
                    return
                await asyncio.sleep(self.idle_sleep)
                continue
            # device step in flight: host-side span — drain newly
            # arrived commands (submits run their prefix match against
            # the *pre-step* index; admission itself happens at the next
            # step_begin) and let the event loop breathe
            self._apply_commands()
            await loop.run_in_executor(self._exec, eng.step_commit,
                                       pending)
            self.steps += 1
            self._pump_events()
