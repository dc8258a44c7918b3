"""Load generation: one thread offers the requests and stamps each with
the instant it was DUE, the instant it was sent, and what came back.

Latency is counted from the due instant: a stall that delays a later
submission is the system's delay, not the generator's luck. How late
the generator itself ran (send - due) is reported beside it, so a
starved generator is not read as a fast server.

``submit(request)`` is the system under test's entry; it returns a
stream with ``finish_reason``, ``token_ids``, ``first_token_time`` and
``finish_time`` (host stamps on ``time.monotonic``), or raises.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

from .traffic import Request


@dataclasses.dataclass
class Row:
    request: Request
    due_t: float                    # monotonic; closed loop: == send_t
    send_t: float
    stream: object = None
    error: Optional[str] = None     # submit raised (shed or refused)
    tokens_in_window: Optional[int] = None   # stamped at window end

    @property
    def done(self) -> bool:
        return self.error is not None or (
            self.stream.finish_reason is not None)

    @property
    def tokens(self) -> int:
        return 0 if self.stream is None else len(self.stream.token_ids)

    @property
    def first_token_t(self) -> Optional[float]:
        return None if self.stream is None else self.stream.first_token_time

    @property
    def finish_t(self) -> Optional[float]:
        return None if self.stream is None else self.stream.finish_time


def _send(submit: Callable, req: Request, due_t: Optional[float]) -> Row:
    send_t = time.monotonic()
    due = send_t if due_t is None else due_t
    try:
        return Row(req, due, send_t, stream=submit(req))
    except Exception as exc:  # noqa: BLE001 — a refusal is a datapoint
        return Row(req, due, send_t, error=f"{type(exc).__name__}: {exc}")


SPIN_S = 0.003     # a sleep wakes up to a millisecond late


def run_open(submit: Callable, requests: list, t0: float) -> list:
    """Open loop: send each request at ``t0 + due_s`` whatever became of
    the ones before. Returns the rows once the last one is sent. The
    last milliseconds before a due instant are spent yielding, not
    asleep: a sleeping thread woke 1 ms late on the chip's host, and a
    millisecond decides which engine round a request joins."""
    rows = []
    for req in requests:
        due_t = t0 + req.due_s
        delay = due_t - time.monotonic() - SPIN_S
        if delay > 0:
            time.sleep(delay)
        while time.monotonic() < due_t:
            time.sleep(0)           # yields the GIL and the core
        rows.append(_send(submit, req, due_t))
    return rows


def run_closed(submit: Callable, next_request: Callable, clients: int,
               t_end: float, poll_s: float = 0.002) -> list:
    """Closed loop: ``clients`` callers, each sending its next request
    when the previous one completes, until ``t_end``. One thread polls
    the streams (a completion is noticed within ``poll_s``); at
    ``t_end`` every unfinished row is stamped with the tokens it had."""
    rows = [_send(submit, next_request(), None) for _ in range(clients)]
    active = list(rows)
    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        for i, row in enumerate(active):
            if row.done:
                active[i] = _send(submit, next_request(), None)
                rows.append(active[i])
        time.sleep(min(poll_s, max(0.0, t_end - time.monotonic())))
    for row in rows:
        row.tokens_in_window = row.tokens
    return rows


def drain(rows: list, deadline_t: float, poll_s: float = 0.005) -> bool:
    """Wait until every row is done or ``deadline_t`` passes; True when
    all finished."""
    pending = [r for r in rows if not r.done]
    while pending and time.monotonic() < deadline_t:
        time.sleep(poll_s)
        pending = [r for r in pending if not r.done]
    return not pending


class Marks:
    """Run callbacks at set instants on a thread of their own, so that a
    slow one (starting the profiler) never delays a submission."""

    def __init__(self, marks: list):
        self._marks = sorted(marks, key=lambda m: m[0])   # (t, fn)
        self._stop = threading.Event()
        self.errors: list = []
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-marks")

    def start(self) -> "Marks":
        self._thread.start()
        return self

    def _run(self) -> None:
        for t, fn in self._marks:
            if self._stop.wait(max(0.0, t - time.monotonic())):
                return
            try:
                fn()
            except Exception as exc:  # noqa: BLE001 — reported by caller
                self.errors.append(repr(exc))

    def finish(self, timeout: float = 240.0) -> None:
        """Let the remaining marks run (they are due by now), then join."""
        self._thread.join(timeout)
        self._stop.set()
        if self._thread.is_alive():
            self.errors.append("marks thread did not end")
