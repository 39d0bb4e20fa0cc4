"""Minimal HTTP/1.1 client of ``POST /v1/plan`` (one request per
connection, as the planner's HTTP front door serves them)."""
from __future__ import annotations

import asyncio
import json
from typing import Optional, Tuple


async def post_plan(host: str, port: int, body: bytes
                    ) -> Tuple[int, Optional[dict]]:
    """Send one plan request; returns (status, decoded JSON body)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"POST /v1/plan HTTP/1.1\r\nHost: {host}\r\n"
                     f"Content-Type: application/json\r\n"
                     f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        raw = await reader.read()
    finally:
        writer.close()
        await writer.wait_closed()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    return status, json.loads(payload) if payload else None
