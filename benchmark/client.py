"""The load generator: ONE process, one asyncio loop, standard library only.

    python benchmark/client.py <plan.json> <records.json>

It reads a plan the traffic kind wrote, sends it to the HTTP proxy over
raw HTTP/1.1 (one connection per request, chunked streaming response, one
JSON token per line — the arithmetic of ``ray_tpu/llm/loadgen.py``'s
client, copied here so that no later PR can change the yardstick), stamps
EVERY token line with the time of the read that delivered it, and writes
one record per request.

Two shapes of plan:

* ``"mode": "open"`` — ``requests`` is a list of ``{"id", "due", "payload"}``;
  each is sent at ``t0 + due`` whatever the server is doing.  Times are
  kept against the DUE instant, so a generator that runs late shows up as
  latency and not as a lighter load; how late it ran is in every record.
* ``"mode": "closed"`` — ``clients`` is a list of clients, each a list of
  sessions, each ``{"system": [tokens], "turns": [{"user": [tokens],
  "max_tokens": n, "think_s": x}, ...]}``.  A client sends a turn, waits
  for the whole reply, appends the user message and the reply to the
  history, thinks, and sends the next.  No turn starts after ``stop_new``.
  ``primers`` (optional) are requests sent one after the other BEFORE the
  clients start, so that shared prefixes are in the server's cache as they
  would be in a deployment that has been up for a while; the clients
  start ``primer_overlap_s`` after the LAST primer was sent, while it
  still streams.

At ``t0 + hard_stop`` every open connection is closed and its record says
``"cut": true``.  All times in the records are seconds from ``t0``.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time


class _Chunked:
    """Incremental decoder of a chunked HTTP/1.1 body into token lines."""

    def __init__(self):
        self.buf = b""
        self.state = "size"  # size line -> data -> the chunk's closing CRLF
        self.need = 0
        self.text = b""
        self.done = False

    def feed(self, data: bytes) -> list:
        """Token lines completed by ``data``."""
        self.buf += data
        while not self.done:
            if self.state == "size":
                i = self.buf.find(b"\r\n")
                if i < 0:
                    break
                self.need = int(self.buf[:i].split(b";", 1)[0], 16)
                self.buf = self.buf[i + 2:]
                if self.need == 0:
                    self.done = True
                else:
                    self.state = "data"
            elif self.state == "data":
                if not self.buf:
                    break
                take = min(self.need, len(self.buf))
                self.text += self.buf[:take]
                self.buf = self.buf[take:]
                self.need -= take
                if self.need == 0:
                    self.state = "crlf"
            else:
                if len(self.buf) < 2:
                    break
                self.buf = self.buf[2:]
                self.state = "size"
        *lines, self.text = self.text.split(b"\n")
        return [ln for ln in lines if ln.strip()]


async def _one_request(port: int, app: str, payload: dict, t0: float, rec: dict):
    """Send one request and read its stream to the end.  ``rec`` is filled
    as the stream goes, so a request cut at the hard stop keeps what it
    had received."""
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps(payload).encode()
        head = (
            f"POST /{app} HTTP/1.1\r\nhost: benchmark\r\n"
            "content-type: application/json\r\n"
            f"content-length: {len(body)}\r\nconnection: close\r\n\r\n"
        )
        rec["sent"] = time.time() - t0
        writer.write(head.encode() + body)
        await writer.drain()
        raw = await reader.readuntil(b"\r\n\r\n")
        rec["status"] = int(raw.split(b" ", 2)[1])
        for line in raw.split(b"\r\n")[1:]:
            if line.lower().startswith(b"x-request-id:"):
                rec["request_id"] = line.split(b":", 1)[1].strip().decode()
        if rec["status"] != 200:
            rec["error"] = (await reader.read(2000)).decode("replace")
            return
        dec = _Chunked()
        while not dec.done:
            data = await reader.read(1 << 16)
            now = time.time() - t0
            if not data:
                break
            for line in dec.feed(data):
                rec["tokens"].append(json.loads(line))
                rec["times"].append(now)
        rec["complete"] = dec.done
        rec["done"] = time.time() - t0
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is a data point
        rec["error"] = repr(e)
    finally:
        if writer is not None:
            writer.close()


def _new_record(rid, due: float, payload: dict, **extra) -> dict:
    return {
        "id": rid, "due": due, "sent": None, "status": 0, "tokens": [],
        "times": [], "complete": False, "cut": False, "done": None,
        "max_tokens": payload["max_tokens"], "prompt_len": len(payload["prompt"]),
        **extra,
    }


async def _sleep_until(t: float):
    delay = t - time.time()
    if delay > 0:
        await asyncio.sleep(delay)


async def _open_loop(plan: dict, records: list):
    t0 = plan["t0"]

    async def fire(req):
        rec = _new_record(req["id"], req["due"], req["payload"])
        records.append(rec)
        await _sleep_until(t0 + req["due"])
        await _one_request(plan["port"], plan["app"], req["payload"], t0, rec)

    await asyncio.gather(*(fire(r) for r in plan["requests"]))


async def _closed_loop(plan: dict, records: list):
    t0 = plan["t0"]
    await _sleep_until(t0)
    last_primer = None
    for i, payload in enumerate(plan.get("primers", [])):
        rec = _new_record(f"prime{i}", time.time() - t0, payload, primer=True)
        records.append(rec)
        last_primer = asyncio.ensure_future(
            _one_request(plan["port"], plan["app"], payload, t0, rec))
        if i + 1 < len(plan["primers"]):
            await last_primer
        else:
            await asyncio.sleep(plan.get("primer_overlap_s", 0.0))
    start = time.time()

    async def client(ci: int, sessions: list):
        await _sleep_until(start + plan.get("stagger_s", 0.0) * ci / max(len(plan["clients"]), 1))
        for si, session in enumerate(sessions):
            history = list(session["system"])
            for ti, turn in enumerate(session["turns"]):
                now = time.time() - t0
                if now >= plan["stop_new"]:
                    return
                payload = dict(
                    turn.get("sampling", {}),
                    prompt=history + turn["user"], max_tokens=turn["max_tokens"],
                )
                rec = _new_record(f"c{ci}s{si}t{ti}", now, payload, client=ci)
                records.append(rec)
                await _one_request(plan["port"], plan["app"], payload, t0, rec)
                if rec["status"] != 200 or not rec["complete"]:
                    return  # a broken session is not continued
                history = payload["prompt"] + rec["tokens"]
                await asyncio.sleep(turn["think_s"])

    await asyncio.gather(*(client(i, s) for i, s in enumerate(plan["clients"])),
                         *([last_primer] if last_primer else []))


async def _main(plan: dict) -> list:
    records: list = []
    loop = _open_loop if plan["mode"] == "open" else _closed_loop
    task = asyncio.ensure_future(loop(plan, records))
    timeout = plan["t0"] + plan["hard_stop"] - time.time()
    done, _ = await asyncio.wait([task], timeout=max(timeout, 0.0))
    if not done:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
    elif task.exception() is not None:
        raise task.exception()
    return records


def main(argv: list) -> None:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    records = asyncio.run(_main(plan))
    with open(out_path + ".tmp", "w") as f:
        json.dump({"t0": plan["t0"], "records": records}, f)
    os.replace(out_path + ".tmp", out_path)


if __name__ == "__main__":
    main(sys.argv[1:])
