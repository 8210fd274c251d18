"""A stand-in for the serving front door, for tests of the load generator:
``POST /v1/completions`` streams ``max_tokens`` tokens, one every
``gap_s``; ``GET /v1/stats`` and ``GET /metrics`` answer with counters."""
from __future__ import annotations

import asyncio
import json


class FakeServer:
    def __init__(self, gap_s: float = 0.001):
        self.gap_s = gap_s
        self.bodies: list[dict] = []
        self.server = None
        self.port = None

    async def start(self):
        self.server = await asyncio.start_server(self._handle, "127.0.0.1", 0)
        self.port = self.server.sockets[0].getsockname()[1]
        return self

    async def stop(self):
        self.server.close()
        await self.server.wait_closed()

    async def _handle(self, reader, writer):
        try:
            line = (await reader.readline()).decode()
            if not line:                    # a client that closed at once
                return
            method, path, _ = line.split()
            n = 0
            while True:
                h = (await reader.readline()).decode().strip()
                if not h:
                    break
                if h.lower().startswith("content-length:"):
                    n = int(h.split(":")[1])
            body = await reader.readexactly(n) if n else b""
            if method == "GET":
                text = (json.dumps({"prefill_tokens": 1, "pending": 0})
                        if path == "/v1/stats" else "x_total 1\n").encode()
                writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n"
                             % len(text) + text)
                await writer.drain()
                return
            req = json.loads(body)
            self.bodies.append(req)
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream"
                         b"\r\n\r\n")
            for i in range(req["max_tokens"]):
                await asyncio.sleep(self.gap_s)
                tok = (sum(req["prompt"]) + i) % 1000
                writer.write(b"data: " + json.dumps(
                    {"token": tok, "index": i}).encode() + b"\n\n")
                await writer.drain()
            writer.write(b'data: {"finish_reason": "complete", "error": null}'
                         b"\n\ndata: [DONE]\n\n")
            await writer.drain()
        except (ConnectionResetError, BrokenPipeError):
            pass
        finally:
            writer.close()
