"""Client-side token times, the measured window, and compiles inside it.

``runtime.Engine.run`` appends every token it serves to ``Request.generated``:
one ``append`` for the first token after prefill, one ``extend`` per fused
decode horizon. The harness hands each request a ``TimedTokens`` list, so
each delivery is stamped on the host clock when a streaming client would
see it, with no change to the program. The same hook opens the window, once
every slot holds a request that has had its first token, and ends it at the
first step that delivers after ``seconds``: that step's tokens are counted
and the window's time runs to its delivery, so the window holds whole steps
and a stall that runs past the nominal close still counts as time. The
next delivery raises ``WindowClosed`` and is not appended.
"""

from __future__ import annotations

import time


class WindowClosed(Exception):
    """Raised from a delivery after the window's close to stop Engine.run."""


class WindowNeverOpened(RuntimeError):
    """Raised from a delivery after ``open_by`` while fewer than
    ``num_slots`` requests are live: the cell cannot fill its slots."""


class Window:
    """Opens when ``num_slots`` requests are live with a first token. The
    first delivery more than ``seconds`` after the open closes it at
    ``close_t``, its own time, and the rest of its step is let through: the
    other requests' tokens of the same fused horizon, each one ``extend``
    to a request not yet seen. A first token (``append``) is a step of its
    own. Any later delivery raises ``WindowClosed``. ``on_open`` runs once,
    at the open, before the clock is read (the traced run starts the
    profiler there). A delivery after ``open_by`` (host time) with the
    window still shut ends the run."""

    def __init__(self, num_slots: int, seconds: float, on_open=None):
        self.num_slots = num_slots
        self.seconds = seconds
        self.on_open = on_open
        self.open_by = float("inf")
        self.open_t: float | None = None
        self.close_t: float | None = None    # the closing delivery
        self.closing: set[int] | None = None  # requests of the closing step
        self.started = 0                     # requests with a first token
        self.finished = 0                    # requests at their budget
        self.live_at_open = 0

    def deliver(self, tokens: TimedTokens, n: int, horizon: bool) -> float:
        t = time.monotonic()
        if self.close_t is not None:
            if (not horizon or self.closing is None
                    or id(tokens) in self.closing):
                raise WindowClosed
            self.closing.add(id(tokens))
            return self.close_t
        if self.open_t is not None:
            if t - self.open_t > self.seconds:
                self.close_t = t
                self.closing = {id(tokens)} if horizon else None
            return t
        first = len(tokens) == 0
        self.started += first
        self.finished += len(tokens) + n >= tokens.budget
        if t > self.open_by:
            raise WindowNeverOpened(
                f"{self.started - self.finished} of {self.num_slots} slots "
                f"live when the window should have opened")
        if self.started - self.finished >= self.num_slots:
            # every slot holds a live request: the steady state the window
            # measures starts here
            self.live_at_open = self.started - self.finished
            if self.on_open is not None:
                self.on_open()
            self.open_t = time.monotonic()
            return self.open_t
        return t


class TimedTokens(list):
    """``Request.generated`` that stamps each delivery; ``times[i]`` is the
    host time at which token ``i`` reached the client."""

    def __init__(self, window: Window, budget: int):
        super().__init__()
        self.window = window
        self.budget = budget
        self.times: list[float] = []

    def append(self, tok) -> None:
        t = self.window.deliver(self, 1, horizon=False)
        super().append(tok)
        self.times.append(t)

    def extend(self, toks) -> None:
        toks = list(toks)
        t = self.window.deliver(self, len(toks), horizon=True)
        super().extend(toks)
        self.times.extend([t] * len(toks))


class CompileCounter:
    """Counts traces, lowerings and XLA compiles (a persistent-cache load
    counts as one) while ``armed``; the window must see none. A jitted call
    that misses its C++ dispatch cache but finds its trace cached passes
    the tracing step in some tens of microseconds: that is dispatch, not a
    trace, so a trace event counts only from a millisecond."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **kw) -> None:
        if self.armed and (event in self.EVENTS
                           or (event == self.TRACE and duration > 1e-3)):
            self.count += 1
            self.names.append(str(kw.get("fun_name", event)))
