"""The client side of a serving cell: one asyncio loop in the benchmark's
own process sends every request over HTTP and times what a user would see.

Times are taken where the user is: a request's clock starts when it was
*due* (an open loop counts the wait a stall imposes on later requests), its
first token is the first SSE chunk that carries text, and tokens are
counted from the text (the benchmark's tokenizer maps one id to one
character).
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time


@dataclasses.dataclass
class Record:
    due: float                  # perf_counter instants
    want: int                   # tokens asked for
    prompt_tokens: int
    tags: dict
    sent: float = 0.0
    first: float = 0.0          # first chunk that carried a token
    last: float = 0.0           # last chunk that carried a token
    done: float = 0.0
    tokens: int = 0
    chunk_times: list = dataclasses.field(default_factory=list)
    error: str = ""

    @property
    def ok(self) -> bool:
        return not self.error and self.tokens == self.want

    @property
    def ttft_ms(self) -> float:
        """Due instant to first token; infinite for a failed request."""
        return (self.first - self.due) * 1e3 if self.ok else float("inf")

    @property
    def tpot_ms(self) -> float:
        """(last token chunk - first) / (tokens - 1)."""
        if not self.ok:
            return float("inf")
        return (self.last - self.first) / max(self.tokens - 1, 1) * 1e3


class Client:
    def __init__(self, port: int, model_id: str, timeout_s: float):
        self.url = f"http://127.0.0.1:{port}/llm/v1/completions"
        self.model_id = model_id
        self.timeout_s = timeout_s
        self.records: list = []
        self._session = None

    async def __aenter__(self):
        import aiohttp
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=self.timeout_s))
        return self

    async def __aexit__(self, *exc):
        await self._session.close()

    def body(self, prompt_ids: list, max_tokens: int) -> bytes:
        return json.dumps({"model": self.model_id, "prompt": prompt_ids,
                           "max_tokens": int(max_tokens), "temperature": 0.0,
                           "stream": True}).encode()

    async def send(self, body: bytes, due: float, want: int,
                   prompt_tokens: int, **tags) -> Record:
        """Sends one streaming completion now; ``due`` is when it should
        have been sent."""
        rec = Record(due=due, want=int(want), prompt_tokens=prompt_tokens,
                     tags=tags)
        self.records.append(rec)
        rec.sent = time.perf_counter()
        try:
            async with self._session.post(
                    self.url, data=body,
                    headers={"Content-Type": "application/json"}) as resp:
                if resp.status != 200:
                    rec.error = f"HTTP {resp.status}"
                    await resp.read()
                async for raw in resp.content:
                    if rec.error or not raw.startswith(b"data: {"):
                        continue
                    now = time.perf_counter()
                    chunk = json.loads(raw[6:])
                    if "error" in chunk:
                        rec.error = str(chunk["error"])
                        continue
                    n = len(chunk["choices"][0]["text"])
                    if n:
                        rec.first = rec.first or now
                        rec.last = now
                        rec.tokens += n
                        rec.chunk_times.append((now, n))
        except (asyncio.TimeoutError, TimeoutError):
            rec.error = "timeout"
        except Exception as e:  # noqa: BLE001 — counted as a failed request
            rec.error = f"{type(e).__name__}: {e}"[:200]
        rec.done = time.perf_counter()
        if not rec.error and rec.tokens != rec.want:
            rec.error = f"got {rec.tokens} tokens, asked {rec.want}"
        return rec


@dataclasses.dataclass
class Window:
    """What a generator is told: where the clock stands and how long."""
    start: float                # perf_counter instant the window opens
    seconds: float

    @property
    def end(self) -> float:
        return self.start + self.seconds


async def sleep_until(t: float) -> None:
    """asyncio.sleep wakes late by up to a millisecond or so; come close
    with it, then yield until the instant."""
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        await asyncio.sleep(left - 0.0015 if left > 0.002 else 0)
