#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX.

    python perfbench/loadgen.py --plan PLAN.json --out RECORDS.jsonl

PLAN.json: {"base": "http://127.0.0.1:PORT", "open_at": <time.time()>,
"send_until": s, "drain_s": s, "requests": [{"id", "due", "prompt",
"max_new_tokens"}, ...]}. Each request is sent at `open_at + due`
whether or not earlier ones have finished (open loop), as a streamed
`POST /generate` with token ids, greedy. After `send_until` nothing
more is sent; requests in flight may finish until `send_until +
drain_s`, then they are cut. One record a request goes to RECORDS:
due, sent, status, every token's arrival (all as seconds from
`open_at`), the tokens, and how it ended.

One thread: an asyncio loop over plain sockets. The server answers a
stream with `Connection: close`, so a response is read to its end.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
import urllib.parse
from typing import Any, Dict, List


async def one_request(host: str, port: int, req: Dict[str, Any],
                      open_at: float, rec: Dict[str, Any]) -> None:
    wait = open_at + req['due'] - time.time()
    if wait > 0:
        await asyncio.sleep(wait)
    body = json.dumps({'tokens': [req['prompt']],
                       'max_new_tokens': req['max_new_tokens'],
                       'temperature': 0.0, 'stream': True}).encode()
    head = (f'POST /generate HTTP/1.1\r\nHost: {host}:{port}\r\n'
            f'Content-Type: application/json\r\n'
            f'Content-Length: {len(body)}\r\nConnection: close\r\n\r\n')
    rec['sent'] = time.time() - open_at
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(head.encode() + body)
        await writer.drain()
        status_line = await reader.readline()
        rec['status'] = int(status_line.split()[1])
        while (await reader.readline()) not in (b'\r\n', b'\n', b''):
            pass
        if rec['status'] != 200:
            rec['end'] = 'refused'
            rec['error'] = (await reader.read(2000)).decode(
                errors='replace')
            return
        while True:
            raw = await reader.readline()
            if not raw:
                rec['end'] = rec.get('end') or 'short'
                return
            line = raw.strip()
            if not line.startswith(b'data: '):
                continue
            payload = line[len(b'data: '):]
            if payload == b'[DONE]':
                rec['end'] = 'done' if rec.get('final') else 'short'
                return
            try:
                event = json.loads(payload)
            except ValueError:
                rec['end'] = 'garbled'
                return
            if 'token' in event:
                rec['arrivals'].append(time.time() - open_at)
                rec['tokens'].append(event['token'])
            elif event.get('done'):
                rec['final'] = True
    finally:
        writer.close()


async def run(plan: Dict[str, Any]) -> List[Dict[str, Any]]:
    url = urllib.parse.urlparse(plan['base'])
    open_at = plan['open_at']
    records, tasks = [], []
    for req in plan['requests']:
        rec = {'id': req['id'], 'due': req['due'],
               'prompt_tokens': len(req['prompt']),
               'max_new_tokens': req['max_new_tokens'], 'sent': None,
               'status': None, 'arrivals': [], 'tokens': [],
               'end': None, 'error': None}
        records.append(rec)
        tasks.append(asyncio.ensure_future(one_request(
            url.hostname, url.port, req, open_at, rec)))
    cut_at = open_at + plan['send_until'] + plan['drain_s']
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, cut_at - time.time()))
    for t in pending:
        t.cancel()
    results = await asyncio.gather(*tasks, return_exceptions=True)
    for rec, res in zip(records, results):
        if isinstance(res, asyncio.CancelledError):
            rec['end'] = 'cut'
        elif isinstance(res, BaseException):
            rec['end'] = 'error'
            rec['error'] = f'{type(res).__name__}: {res}'
        rec.pop('final', None)
    return records


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument('--plan', required=True)
    parser.add_argument('--out', required=True)
    args = parser.parse_args()
    with open(args.plan, 'r', encoding='utf-8') as f:
        plan = json.load(f)
    records = asyncio.run(run(plan))
    with open(args.out, 'w', encoding='utf-8') as f:
        for rec in records:
            f.write(json.dumps(rec) + '\n')
    return 0


if __name__ == '__main__':
    sys.exit(main())
