"""The load client: one thread that sends a plan of sessions into the kernel.

A plan is a list of ``Session``s. A session's first turn is due at its
``due_s``; each later turn is due ``think_s`` after the reply to the turn
before it, so a session is a closed loop inside and sessions arrive on an
open schedule. A turn either extends the conversation (the previous prompt,
the tokens served for it, then the turn's new tokens) or starts a fresh one
(``reset``), which is how a closed-loop client is written.

Every request is timed from when it was due. Each streamed token is stamped
with the host clock and the engine's tick count at the moment the engine
hands it to the request's channel; each admission is stamped the same way.
"""
from __future__ import annotations

import dataclasses
import heapq
import threading
import time
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Turn:
    new: np.ndarray          # tokens this turn adds (the whole prompt if reset)
    max_new: int             # tokens to generate, greedy
    think_s: float = 0.0     # pause after the reply before the next turn
    reset: bool = False      # start a fresh conversation with ``new`` alone


@dataclasses.dataclass
class Session:
    due_s: float             # first turn due, seconds after traffic starts
    turns: List[Turn]
    prefix: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32))
    cap: int = 1 << 30       # the session ends before a turn would exceed
                             # this context (prompt + new tokens)


def request_class(base, tick_index: Callable[[], int]):
    """A streaming LLM syscall type that stamps its tokens and admissions.
    ``base`` is the kernel's LLM syscall class; ``tick_index`` reads the
    engine's tick count on the thread that produces the token."""

    class Request(base):
        def __init__(self, prompt: np.ndarray, max_new: int, due: float,
                     sid: int, turn: int):
            super().__init__(f"session{sid}", {
                "prompt": [int(t) for t in prompt],
                "max_new_tokens": int(max_new), "temperature": 0.0,
                "stream": True, "stream_buffer": int(max_new) + 8})
            self.prompt_np = np.asarray(prompt, np.int32)
            self.max_new = int(max_new)
            self.due = due
            self.sid = sid
            self.turn = turn
            self.sent: Optional[float] = None
            self.stamps: List[tuple] = []     # (time, tick) per token
            self.admits: List[tuple] = []     # (time, tick) per admission
            self.ended_by_window = False

        def mark_running(self):
            self.admits.append((time.monotonic(), tick_index()))
            super().mark_running()

        def push_token(self, token: int):
            self.stamps.append((time.monotonic(), tick_index()))
            super().push_token(token)

    return Request


class Client:
    """Sends ``sessions`` into ``kernel`` from ``t0`` (host monotonic clock)
    until ``t_stop``; a turn due at or after ``t_stop`` is never sent."""

    def __init__(self, kernel, request_cls, sessions: List[Session],
                 t0: float, t_stop: float):
        self.kernel = kernel
        self.request_cls = request_cls
        self.sessions = sessions
        self.t0 = t0
        self.t_stop = t_stop
        self.requests: List = []
        self._heap: List[tuple] = []
        self._seq = 0
        self._cond = threading.Condition()
        self._stopped = False
        self._thread: Optional[threading.Thread] = None
        self._conv: List[np.ndarray] = [s.prefix for s in sessions]
        self.errors: List[str] = []

    def _push(self, due: float, sid: int, turn: int):
        with self._cond:
            heapq.heappush(self._heap, (due, self._seq, sid, turn))
            self._seq += 1
            self._cond.notify()

    def start(self):
        for sid, s in enumerate(self.sessions):
            if s.turns:
                self._push(self.t0 + s.due_s, sid, 0)
        self._thread = threading.Thread(target=self._run, name="bench-load",
                                        daemon=True)
        self._thread.start()

    def stop(self):
        with self._cond:
            self._stopped = True
            self._cond.notify()
        if self._thread is not None:
            self._thread.join(timeout=30)

    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self):
        while True:
            with self._cond:
                while not self._stopped:
                    now = time.monotonic()
                    if self._heap and self._heap[0][0] <= now:
                        break
                    wait = 0.05 if not self._heap else min(
                        0.05, self._heap[0][0] - now)
                    self._cond.wait(timeout=max(wait, 0.0))
                if self._stopped:
                    return
                due, _, sid, turn = heapq.heappop(self._heap)
            if due >= self.t_stop:
                continue
            self._send(due, sid, turn)

    def _send(self, due: float, sid: int, turn_idx: int):
        s = self.sessions[sid]
        turn = s.turns[turn_idx]
        prompt = turn.new if turn.reset else np.concatenate(
            [self._conv[sid], turn.new])
        if len(prompt) + turn.max_new > s.cap:
            return
        req = self.request_cls(prompt, turn.max_new, due, sid, turn_idx)
        req.add_done_callback(self._done)
        self.requests.append(req)
        req.sent = time.monotonic()
        self.kernel.submit(req)

    def _done(self, req):
        if req.status != "done":
            if not req.cancelled:
                self.errors.append(f"request {req.pid}: {req.error}")
            return
        sid = req.sid
        self._conv[sid] = np.concatenate(
            [req.prompt_np, np.asarray(req.response["tokens"], np.int32)])
        nxt = req.turn + 1
        s = self.sessions[sid]
        if nxt < len(s.turns):
            self._push(time.monotonic() + s.turns[req.turn].think_s, sid,
                       nxt)
