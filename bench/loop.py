"""One measured window: the generator's requests go to the engine through
``Engine.submit``, the engine ticks through ``Engine.step``, and the host
records when each request was due, sent, admitted and when each of its
tokens showed, together with the config and the busy time of every tick.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

from bench import generator, program

TERMINAL = frozenset({"done", "rejected", "expired", "failed"})


@dataclass
class Record:
    """What the host saw of one request.  Steps are 0-based indices into
    ``Window.steps``; times are seconds from the window's start."""
    spec: generator.Spec
    req: object
    due_s: float
    submit_s: float
    admit_step: int | None = None
    admit_s: float | None = None
    token_s: list = field(default_factory=list)
    token_step: list = field(default_factory=list)

    @property
    def status(self) -> str:
        return self.req.status


@dataclass
class Step:
    start_s: float
    end_s: float
    config: int
    active: int              # occupied decode slots when the tick began


@dataclass
class Window:
    seconds: float
    records: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    close_step: int = 0      # steps that began before the window closed
    end_s: float = 0.0       # when the last request finished (or the cap)
    drain_capped: bool = False
    open_loop: bool = True

    def attempted(self) -> list:
        """Requests that count: in an open loop every arrival in the
        window, in a saturated loop every request the window admitted."""
        if self.open_loop:
            return [r for r in self.records if r.due_s < self.seconds]
        return [r for r in self.records
                if r.admit_step is not None and r.admit_step < self.close_step]

    def failed(self) -> list:
        """Attempted requests that did not finish with every token."""
        return [r for r in self.attempted() if r.status != "done"]


class Tracer:
    """Starts and stops the profiler at fixed times inside the window."""

    def __init__(self, log_dir: str, start_s: float, stop_s: float):
        self.log_dir, self.start_s, self.stop_s = log_dir, start_s, stop_s
        self.on = self.done = False
        self.started_step = self.stopped_step = None
        self.snapshot = lambda tag: None     # engine counters at start/stop

    def poll(self, now: float, step: int) -> None:
        import jax
        if not self.on and not self.done and now >= self.start_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.snapshot("start")
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.on, self.started_step = True, step
        elif self.on and now >= self.stop_s:
            self.stop(step)

    def stop(self, step: int) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.snapshot("stop")
            self.on, self.done, self.stopped_step = False, True, step


def run(eng, mix: dict, seconds: float, seed: int, vocab: int, *,
        clock=time.perf_counter, annotate=None, tracer: Tracer | None = None
        ) -> Window:
    """Drive `eng` through a window of `seconds` under `mix`, then drain."""
    ann = annotate or (lambda name: contextlib.nullcontext())
    open_loop = mix["loop"] == "open"
    specs = generator.open_loop(mix, seed, seconds, vocab) if open_loop \
        else None
    feed = None if open_loop else generator.saturated(mix, seed, vocab)
    depth = mix.get("queue_depth", 0)
    cap = mix["drain_cap_s"]
    win = Window(seconds=seconds, open_loop=open_loop)
    inflight: list[Record] = []
    nxt, cur, closed = 0, None, False
    t0 = clock()

    def submit(spec, now):
        req = program.new_request(spec)
        rec = Record(spec, req, spec.due_s if open_loop else now, now)
        eng.submit(req)
        win.records.append(rec)
        if req.status not in TERMINAL:
            inflight.append(rec)

    while True:
        now = clock() - t0
        if tracer is not None:
            tracer.poll(now, len(win.steps))
        if not closed and now >= seconds:
            closed, win.close_step = True, len(win.steps)
            if not open_loop:
                eng.drain()
        c = generator.config_at(mix, now)
        if c != cur:
            eng.set_approx_cfg(c)
            cur = c
        if not closed:
            with ann("bench.submit"):
                if open_loop:
                    while nxt < len(specs) and specs[nxt].due_s <= now:
                        submit(specs[nxt], clock() - t0)
                        nxt += 1
                else:
                    queued = sum(r.req.status == "queued" for r in inflight)
                    for _ in range(depth - queued):
                        submit(next(feed), clock() - t0)
        if closed and not open_loop:
            inflight = [r for r in inflight if r.admit_step is not None]
        if not inflight:
            if closed:
                break
            wake = specs[nxt].due_s if open_loop and nxt < len(specs) \
                else seconds
            with ann("bench.wait"):
                time.sleep(max(min(wake, seconds) - (clock() - t0), 0.0))
            continue
        if closed and now > seconds + cap:
            win.drain_capped = True
            break
        active = eng.backpressure["active"]
        with ann("bench.step"):
            eng.step()
        end = clock() - t0
        step = len(win.steps)
        win.steps.append(Step(now, end, cur, active))
        with ann("bench.bookkeeping"):
            for r in inflight:
                n = len(r.req.tokens)
                if n > len(r.token_s):
                    r.token_s.extend([end] * (n - len(r.token_s)))
                    r.token_step.extend([step] * (n - len(r.token_step)))
                if r.admit_step is None and r.req.status in ("active",
                                                             "done"):
                    r.admit_step, r.admit_s = step, end
            inflight = [r for r in inflight if r.req.status not in TERMINAL]
    win.end_s = clock() - t0
    if tracer is not None:
        tracer.stop(len(win.steps))
    return win
