"""Closed-loop robot fleet: the one traffic generator, driven by a mix file.

Each robot holds a fixed instruction and, every control step, sends a new
camera frame with it and waits for the whole answer (reasoning tokens, then
action tokens) before it sends the next one, with no think time between.
Robots start ``stagger_s`` apart, in an order drawn from the seed. The
measured window opens when every robot has finished one step and closes at
the first step completion ``seconds`` later, so it always spans whole
steps; the steps still in flight then are cut off and not judged.

Everything is drawn from the seed: instructions, frames and start order.
Every seed gives the same sizes and the same start times, in another
order. Frames are unique per step (a base frame from a seeded pool with
the step's number written into its first patch), so no two steps share a
prefix.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

FRAME_POOL = 64


@dataclass
class Step:
    robot: int
    index: int
    t_submit: float                  # client clock, before submit()
    request: object = None           # the engine's Request
    t_first: Optional[float] = None  # first token delivered to the robot
    t_done: Optional[float] = None   # last token delivered, stream closed
    token_times: List[float] = field(default_factory=list)

    @property
    def tokens(self) -> List[int]:
        return list(self.request.out_tokens)


class Fleet:
    def __init__(self, traffic: dict, frame_shape, vocab_size: int,
                 seed: int):
        self.robots = int(traffic["robots"])
        self.new_tokens = (int(traffic["cot_tokens"])
                           + int(traffic["action_tokens"]))
        rng = np.random.default_rng([seed, 0x7F1EE7])
        self.instructions = rng.integers(
            0, vocab_size, (self.robots, int(traffic["instruction_tokens"])),
            dtype=np.int32)
        self.pool = rng.standard_normal(
            (FRAME_POOL,) + tuple(frame_shape), np.float32).astype(
                jnp.bfloat16)
        self.pool_offset = int(rng.integers(0, FRAME_POOL))
        order = rng.permutation(self.robots)
        self.start_s = np.empty(self.robots)
        self.start_s[order] = np.arange(self.robots) * float(
            traffic["stagger_s"])
        self.steps: List[Step] = []
        self.done_robots = set()
        self.window_open: Optional[float] = None
        self.window_close: Optional[float] = None
        self.stopping = False
        self._uid = 0

    def frame(self) -> np.ndarray:
        """The next unique camera frame: a pooled frame with this step's
        number written, base 128, into its first four features."""
        uid, self._uid = self._uid, self._uid + 1
        f = self.pool[(uid + self.pool_offset) % FRAME_POOL].copy()
        f[0, :4] = [(uid >> (7 * i)) & 127 for i in range(4)]
        return f

    def counted(self) -> List[Step]:
        """Steps completed inside the window (after it opened, up to and
        including the completion that closed it)."""
        return [s for s in self.steps if s.t_done is not None
                and self.window_open < s.t_done <= self.window_close]

    async def _robot(self, fe, r: int, t0: float, seconds: float,
                     on_open: Callable, on_close: Callable):
        await asyncio.sleep(max(0.0, t0 + self.start_s[r]
                                - time.perf_counter()))
        k = 0
        while not self.stopping:
            with jax.profiler.TraceAnnotation("bench.submit"):
                frame = self.frame()
                step = Step(r, k, time.perf_counter())
                stream = await fe.submit(self.instructions[r],
                                         self.new_tokens, patches=frame)
            step.request = stream.request
            self.steps.append(step)
            async for _ in stream:
                step.token_times.append(time.perf_counter())
            step.t_done = time.perf_counter()
            if step.token_times:
                step.t_first = step.token_times[0]
            self.done_robots.add(r)
            if self.window_open is None \
                    and len(self.done_robots) == self.robots:
                self.window_open = step.t_done
                on_open()
            elif (self.window_open is not None
                  and self.window_close is None
                  and step.t_done >= self.window_open + seconds):
                self.window_close = step.t_done
                self.stopping = True
                on_close()
            k += 1

    async def run(self, fe, seconds: float, on_open: Callable,
                  on_close: Callable, t0: float):
        """Drive the fleet through warm-up and the window; cut the steps
        still in flight at the close. Returns how many were cut."""
        tasks = [asyncio.ensure_future(
            self._robot(fe, r, t0, seconds, on_open, on_close))
            for r in range(self.robots)]
        while self.window_close is None:
            done = [t for t in tasks if t.done()]
            for t in done:
                t.result()          # a robot that failed fails the run
            await asyncio.sleep(0.05)
        for t in tasks:
            if t.done():
                t.result()
            else:
                t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return sum(s.t_done is None for s in self.steps)
