"""Emulated edge fleet: one process, one TCP connection per stream.

Each stream speaks the program's own protocol (``repro.transport.codec``)
to the server over localhost TCP: ``Hello`` -> ``Admit`` -> (``DraftPacket``
-> ``Verdict``) x rounds -> ``Close``.  A device "drafts" by sleeping for
its board's drafting time (k tokens at the traffic file's draft rate), then
sends k token ids drawn from the seed; an echo stream sends the target's
own greedy continuation instead, so its drafts are accepted.  The fleet
never falls back to local tokens: a round with no verdict within the
timeout is a failed operation.

The fleet touches no accelerator (it is started with ``JAX_PLATFORMS=cpu``)
and runs apart from the server, so the server's device syncs never make
the load late.  It talks to the server process over its pipes:

    stdin  <- {"port", "spec", "seed", "seconds", "echo": {sid: [tokens]}}
    stdout -> READY                                   (pre-admitted streams connected)
    stdin  <- GO <t_open>                             (time.monotonic() of the window's start)
    stdout -> {...}                                   (one JSON line of records, after the window)

The server process starts it as ``python3 fleet.py`` and drives it alone.
"""
from __future__ import annotations

import asyncio
import gc
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import numpy as np  # noqa: E402

import schedule as sched_mod  # noqa: E402


class StreamRec:
    __slots__ = ("sid", "due", "t_admit", "t_first", "t_last", "done", "tokens", "accepted",
                 "finished", "failed")

    def __init__(self, s):
        self.sid, self.due = s.sid, s.due
        self.t_admit = self.t_first = self.t_last = None
        self.done = 0
        self.tokens: List[int] = []
        self.accepted = 0
        self.finished = self.failed = False


class Fleet:
    def __init__(self, schedule, port: int, echo: Dict[int, List[int]], seed: int,
                 vocab: int, timeout_s: float):
        from repro.transport import codec
        from repro.transport.links import tcp_connect

        self.codec, self.tcp_connect = codec, tcp_connect
        self.schedule, self.port, self.echo = schedule, port, echo
        self.seed, self.vocab, self.timeout_s = seed, vocab, timeout_s
        self.recs = {s.sid: StreamRec(s) for s in schedule.streams}
        # made before the window: seeding a generator costs about 0.3 ms,
        # and every stream starts within the first round period
        self.rngs = {s.sid: np.random.default_rng([seed & 0xFFFFFFFFFFFF, s.sid])
                     for s in schedule.streams}
        self.rounds: List[list] = []  # [t_send, t_recv, queue_s, n_tokens, n_accepted, sid]
        self.timeouts = 0
        self.errors: List[str] = []
        self.lateness: List[float] = []
        self.eps: Dict[int, object] = {}
        self.t_open = 0.0
        self.cpu_s = 0.0
        self.loop_stall = (0.0, 0.0)  # (seconds, when)

    async def _recv(self, ep, timeout: Optional[float]):
        frame = await asyncio.wait_for(ep.recv(), timeout)
        if frame is None:
            raise ConnectionError("server closed the link")
        return self.codec.decode_frame(frame)[0]

    async def _admit(self, s) -> object:
        ep = await self.tcp_connect("127.0.0.1", self.port)
        self.eps[s.sid] = ep
        await ep.send(self.codec.encode_frame(self.codec.Hello(s.sid, s.prompt)))
        while True:  # Admit(ok=False): the pool is full, the server queued us
            msg = await self._recv(ep, None)
            if isinstance(msg, self.codec.Admit) and msg.ok:
                return ep

    async def preadmit(self) -> None:
        await asyncio.gather(*(self._admit(s) for s in self.schedule.pre_admitted))

    async def _sleep_until(self, t: float) -> None:
        loop = asyncio.get_running_loop()
        dt = t - loop.time()
        if dt > 0:
            await asyncio.sleep(dt)
        self.lateness.append(max(loop.time() - t, 0.0))

    def _drafts(self, s, rec: StreamRec, rng) -> np.ndarray:
        cont = self.echo.get(s.sid)
        n = len(rec.tokens)
        if cont is not None and rec.tokens == cont[:n] and n + s.k <= len(cont):
            return np.asarray(cont[n:n + s.k], np.int32)
        return rng.integers(0, self.vocab, s.k).astype(np.int32)

    async def play(self, s) -> None:
        loop = asyncio.get_running_loop()
        rec = self.recs[s.sid]
        rng = self.rngs[s.sid]
        try:
            if s.due is None:
                ep = self.eps[s.sid]
                t_next = self.t_open + s.phase
            else:
                await self._sleep_until(self.t_open + s.due)
                ep = await self._admit(s)
                rec.t_admit = loop.time()
                t_next = rec.t_admit + s.draft_s
            for r in range(s.rounds):
                await self._sleep_until(t_next)
                toks = self._drafts(s, rec, rng)
                t_send = loop.time()
                await ep.send(self.codec.encode_frame(self.codec.DraftPacket(s.sid, r, toks)))
                while True:
                    try:
                        msg = await self._recv(ep, self.timeout_s)
                    except asyncio.TimeoutError:
                        self.timeouts += 1
                        rec.failed = True
                        return
                    if isinstance(msg, self.codec.Verdict) and msg.seq == r:
                        break
                t_recv = loop.time()
                out = [int(t) for t in msg.tokens]
                self.rounds.append([t_send, t_recv, float(msg.queue_s), len(out),
                                    int(msg.n_accepted), s.sid])
                if rec.t_first is None:
                    rec.t_first = t_recv
                rec.t_last = t_recv
                rec.tokens.extend(out)
                rec.accepted += int(msg.n_accepted)
                rec.done += 1
                t_next = t_recv + s.draft_s
            rec.finished = True
            await ep.send(self.codec.encode_frame(self.codec.Close(s.sid)))
        except ConnectionError as e:
            rec.failed = True
            self.errors.append(f"stream {s.sid}: {e}")

    async def _watch_loop(self, t_close: float) -> None:
        """Longest time the event loop did not get round to a 5 ms tick."""
        loop = asyncio.get_running_loop()
        t = loop.time()
        while t < t_close:
            await asyncio.sleep(0.005)
            now = loop.time()
            if now - t - 0.005 > self.loop_stall[0]:
                self.loop_stall = (now - t - 0.005, t - self.t_open)
            t = now

    async def run(self, t_open: float) -> None:
        self.t_open = t_open
        t_close = t_open + self.schedule.seconds
        cpu0 = time.process_time()
        tasks = [asyncio.ensure_future(self.play(s)) for s in self.schedule.streams
                 if s.due is None or s.due < self.schedule.seconds]
        tasks.append(asyncio.ensure_future(self._watch_loop(t_close)))
        await asyncio.sleep(max(t_close - asyncio.get_running_loop().time(), 0.0))
        for t in tasks:
            t.cancel()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        for r in results:
            if isinstance(r, Exception) and not isinstance(r, asyncio.CancelledError):
                self.errors.append(repr(r))
        self.cpu_s = time.process_time() - cpu0
        for ep in self.eps.values():
            ep.close()

    def records(self) -> dict:
        return {
            "t_open": self.t_open,
            "t_close": self.t_open + self.schedule.seconds,
            "rounds": self.rounds,
            "timeouts": self.timeouts,
            "errors": self.errors[:20],
            "lateness_p99_s": sched_mod.percentile(self.lateness, 99) if self.lateness else 0.0,
            "lateness_max_s": max(self.lateness, default=0.0),
            "cpu_s": self.cpu_s,
            "loop_stall": self.loop_stall,
            "streams": [
                {"sid": r.sid, "due": r.due, "t_admit": r.t_admit, "t_first": r.t_first,
                 "t_last": r.t_last, "done": r.done, "finished": r.finished, "failed": r.failed,
                 "accepted": r.accepted, "tokens": r.tokens if r.finished else []}
                for r in self.recs.values()
            ],
        }


async def main_async() -> dict:
    loop = asyncio.get_running_loop()
    hello = json.loads(await loop.run_in_executor(None, sys.stdin.readline))
    spec, seed, seconds = hello["spec"], int(hello["seed"]), float(hello["seconds"])
    vocab = int(spec["config"]["vocab_size"])
    schedule = sched_mod.build(spec["traffic"], spec["serving"], vocab, seed, seconds)
    echo = {int(k): v for k, v in hello["echo"].items()}
    fleet = Fleet(schedule, int(hello["port"]), echo, seed, vocab,
                  float(spec["traffic"]["verify_timeout_s"]))
    await fleet.preadmit()
    gc.collect()
    gc.freeze()
    gc.disable()  # no collection pauses the load inside the window
    print("READY", flush=True)
    line = await loop.run_in_executor(None, sys.stdin.readline)
    word, t_open = line.split()
    if word != "GO":
        raise RuntimeError(f"expected GO, got {line!r}")
    await fleet.run(float(t_open))
    return fleet.records()


def main() -> int:
    rec = asyncio.run(main_async())
    sys.stdout.write(json.dumps(rec) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
