#!/usr/bin/env python3
"""The load generator: one general reader of traffic files, closed loop.

Standard library only.  ``run.py`` starts this file as a child process, so
the clients' threads never share the serving process's interpreter lock; it
also imports the schedule and the percentile arithmetic from here.

A traffic mix is data (the ``traffic`` object of a
``benchmark/workloads/<cell>.json``)::

    {"loop": "closed", "clients": 8,
     "prompt_tokens": {"dist": "lognormal", "median": 350, "sigma": 0.4,
                       "min": 257, "max": 512},
     "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.5,
                       "min": 16, "max": 512},
     "pool": 256, "pool_seed": 1, "greedy_every": 8,
     "temperature": 1.0, "top_k": 40, "speculative": false, "settle_s": 6}

Sizes: ``pool`` (prompt, output) pairs are drawn once from ``pool_seed`` —
the same set whatever ``--seed`` is.  ``--seed`` only shuffles their order,
deals them to the clients, and draws the token ids and the sampling seeds:
every seed offers the same work in another order, so runs with different
seeds differ by no more than runs of one.

Every ``greedy_every``-th request of a client decodes greedily
(``temperature`` 0, ``"speculative": false``): only a greedy token can be
held against a reference.  All others sample with a seed of their own.

Prompts are token ids written out (``benchmark/idtok``): ``n`` prompt tokens
are ``n - 1`` distinct seeded ids behind the BOS the server adds, so no two
prompts share a first block and the prefix cache never hits.

Clock: ``time.time()`` throughout (the parent places its trace window by the
window bounds this process reports).  A request's TTFT runs from the moment
its bytes are handed to the socket to the first streamed chunk that carries a
token; its TPOT is (last token chunk - first) / (tokens - 1).
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import random
import sys
import threading
import time
from typing import Dict, List, Optional
from urllib.parse import urlparse

FIRST_FREE_ID = 3  # 0 pad, 1 BOS, 2 EOS


# ------------------------------------------------------------- arithmetic
def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default), the
    arithmetic of ``tools/replay.py::_pct``."""
    if not values:
        return None
    vals = sorted(values)
    rank = q / 100.0 * (len(vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (rank - lo)


def draw(spec: Dict, rng: random.Random) -> int:
    """One whole number from a length distribution, clipped to its range."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = spec["median"] * math.exp(rng.gauss(0.0, spec["sigma"]))
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi)
    elif spec["dist"] == "fixed":
        x = spec["value"]
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return max(lo, min(hi, int(round(x))))


# --------------------------------------------------------------- schedule
def size_pool(traffic: Dict) -> List[List[int]]:
    """The fixed set of (prompt tokens, output tokens) pairs of a mix."""
    rng = random.Random(f"pool:{traffic.get('pool_seed', 0)}")
    return [[draw(traffic["prompt_tokens"], rng),
             draw(traffic["output_tokens"], rng)]
            for _ in range(int(traffic["pool"]))]


def client_schedules(traffic: Dict, seed: int) -> List[List[Dict]]:
    """For each client its requests, in order: sizes only (ids are drawn
    when a request is sent).  The pool is shuffled by ``seed`` and dealt
    round-robin; a client that exhausts its share starts over."""
    pool = size_pool(traffic)
    random.Random(f"order:{seed}").shuffle(pool)
    clients = int(traffic["clients"])
    every = int(traffic.get("greedy_every", 0))
    out = []
    for c in range(clients):
        share = pool[c::clients]
        reqs = []
        for j, (n_prompt, n_out) in enumerate(share):
            # stagger the greedy ones over the clients
            greedy = bool(every) and (j + c) % every == 0
            reqs.append({"client": c, "index": j, "prompt_tokens": n_prompt,
                         "n_predict": n_out, "greedy": greedy})
        out.append(reqs)
    return out


def prompt_ids(seed: int, tag: str, n_prompt: int, vocab: int) -> List[int]:
    """``n_prompt - 1`` seeded ids (the server puts BOS in front)."""
    rng = random.Random(f"ids:{seed}:{tag}")
    return [rng.randrange(FIRST_FREE_ID, vocab)
            for _ in range(max(1, n_prompt - 1))]


def request_body(traffic: Dict, seed: int, req: Dict, vocab: int,
                 tag: str = "") -> Dict:
    tag = tag or f"{req['client']}:{req['index']}:{req.get('lap', 0)}"
    ids = prompt_ids(seed, tag, req["prompt_tokens"], vocab)
    greedy = req["greedy"]
    body = {"prompt": "".join(f"{i} " for i in ids),
            "n_predict": req["n_predict"], "stream": True,
            "temperature": 0.0 if greedy else traffic.get("temperature", 1.0),
            "top_k": traffic.get("top_k", 40),
            "seed": random.Random(f"sample:{seed}:{tag}").randrange(2 ** 31)}
    if greedy or not traffic.get("speculative", True):
        # a greedy row must take the same plain decode the sampled rows
        # take: random weights cycle under greedy decoding, and prompt-
        # lookup speculation would then win on an artefact.  A mix with
        # "speculative": false opts every request out (see PERF.md)
        body["speculative"] = False
    return body, ids


# ------------------------------------------------------------------ client
def send_one(conn_box: List, host: str, port: int, body: Dict,
             timeout: float) -> Dict:
    """POST one streamed /completion; returns the record of what came."""
    rec = {"status": 0, "error": None, "tokens": [], "chunks": [],
           "t_send": time.time(), "t_first": None, "t_last": None,
           "final": None}
    payload = json.dumps(body).encode()
    try:
        if conn_box[0] is None:
            conn_box[0] = http.client.HTTPConnection(host, port,
                                                     timeout=timeout)
        conn = conn_box[0]
        rec["t_send"] = time.time()
        conn.request("POST", "/completion", body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(2000).decode("utf-8", "replace") or "?"
            rec["t_done"] = time.time()
            return rec
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.time()
            event = json.loads(line[6:])
            if event.get("error"):
                rec["error"] = str(event["error"])
            text = event.get("content") or ""
            if text:
                if rec["t_first"] is None:
                    rec["t_first"] = now
                rec["t_last"] = now
                ids = [int(p) for p in text.split()]
                rec["tokens"].extend(ids)
                rec["chunks"].append([now, len(ids)])
            if event.get("stop"):
                rec["final"] = {k: event.get(k) for k in
                                ("tokens_evaluated", "tokens_predicted",
                                 "stopped_eos", "timings")}
                resp.read()  # drain to the end of the chunked body
                break
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        try:
            if conn_box[0] is not None:
                conn_box[0].close()
        finally:
            conn_box[0] = None
    rec["t_done"] = time.time()
    return rec


def burst(url: str, bodies: List[Dict], timeout: float = 900.0) -> List[Dict]:
    """Send ``bodies`` at the same moment, one connection each (the
    connections are opened first, then a barrier, then the sends)."""
    u = urlparse(url)
    out: List[Optional[Dict]] = [None] * len(bodies)
    gate = threading.Barrier(len(bodies))

    def one(i):
        box = [http.client.HTTPConnection(u.hostname, u.port,
                                          timeout=timeout)]
        box[0].connect()
        gate.wait()
        out[i] = send_one(box, u.hostname, u.port, bodies[i], timeout)
        if box[0] is not None:
            box[0].close()

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def closed_loop(url: str, traffic: Dict, seed: int, vocab: int,
                seconds: float, announce=None, timeout: float = 120.0
                ) -> Dict:
    """``clients`` callers, each sending its next request when the previous
    reply ends.  They run ``settle_s`` seconds unrecorded, then the window of
    ``seconds``; at its close no new request is sent and those in flight are
    awaited (``drain``).  Returns the window bounds and every record."""
    u = urlparse(url)
    schedules = client_schedules(traffic, seed)
    settle = float(traffic.get("settle_s", 5))
    t0 = time.time()
    w0, w1 = t0 + settle, t0 + settle + seconds
    records: List[Dict] = []
    lock = threading.Lock()

    def client(c: int):
        box = [None]
        reqs, j, lap = schedules[c], 0, 0
        # callers start spread over the first second, not as one burst
        time.sleep(c / max(1, len(schedules)))
        while time.time() < w1:
            req = dict(reqs[j], lap=lap)
            body, ids = request_body(traffic, seed, req, vocab)
            rec = send_one(box, u.hostname, u.port, body, timeout)
            rec.update(client=c, index=req["index"], lap=lap,
                       greedy=req["greedy"], n_predict=req["n_predict"],
                       prompt_tokens=req["prompt_tokens"])
            if req["greedy"]:
                rec["prompt_ids"] = ids
            else:
                rec["tokens"] = len(rec["tokens"])  # the count is enough
            with lock:
                records.append(rec)
            j += 1
            if j == len(reqs):
                j, lap = 0, lap + 1
        if box[0] is not None:
            box[0].close()

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(len(schedules))]
    for t in threads:
        t.start()
    if announce is not None:
        announce(w0, w1)
    for t in threads:
        t.join(timeout=settle + seconds + timeout + 60)
    return {"window": [w0, w1], "records": records,
            "stuck_clients": sum(t.is_alive() for t in threads)}


def n_tokens(rec: Dict) -> int:
    return rec["tokens"] if isinstance(rec["tokens"], int) else len(
        rec["tokens"])


def reduce_window(result: Dict) -> Dict:
    """End-to-end numbers of a window.

    Rates are over all the work and all the time of the window: every token
    that reached a client between its bounds counts, whichever request it
    belongs to (one that began before the window or ends after it too), and
    a prompt counts at the moment its first token arrives, when its prefill
    is done.  Crediting a request's tokens only where it completes would
    move a 512-token answer in or out of the window whole.

    Tails are over ALL requests completed inside the window.  A failed
    request misses every latency limit: it enters the tails as the length of
    the window."""
    w0, w1 = result["window"]
    seconds = w1 - w0
    done = [r for r in result["records"] if w0 <= r["t_done"] <= w1]
    ok = [r for r in done if r["status"] == 200 and not r["error"]
          and n_tokens(r) > 0]
    miss_ms = seconds * 1e3
    ttft = [(r["t_first"] - r["t_send"]) * 1e3 for r in ok]
    tpot = [(r["t_last"] - r["t_first"]) * 1e3 / (n_tokens(r) - 1)
            for r in ok if n_tokens(r) > 1]
    failed = len(done) - len(ok)
    ttft += [miss_ms] * failed
    tpot += [miss_ms] * failed
    sound = [r for r in result["records"]
             if r["status"] == 200 and not r["error"]]
    out_tokens = sum(n for r in sound for t, n in r["chunks"]
                     if w0 <= t <= w1)
    prompt_tokens = sum(r["prompt_tokens"] for r in sound
                        if r["t_first"] is not None
                        and w0 <= r["t_first"] <= w1)
    return {"attempted": len(done), "failed": failed,
            "window_s": seconds,
            "output_tokens": out_tokens, "prompt_tokens": prompt_tokens,
            "output_tokens_per_s": out_tokens / seconds,
            "prompt_tokens_per_s": prompt_tokens / seconds,
            "requests_per_s": len(ok) / seconds,
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p90_ms": percentile(ttft, 90),
            "tpot_p50_ms": percentile(tpot, 50),
            "tpot_p90_ms": percentile(tpot, 90)}


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--url", required=True)
    ap.add_argument("--traffic", required=True,
                    help="JSON file whose 'traffic' object is the mix")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--out", required=True, help="where the records go")
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)["traffic"]

    def announce(w0, w1):
        print(json.dumps({"window": [w0, w1]}), flush=True)

    result = closed_loop(args.url, traffic, args.seed, args.vocab,
                         args.seconds, announce)
    with open(args.out, "w") as f:
        json.dump(result, f)
    print(json.dumps({"done": True, "records": len(result["records"]),
                      "stuck_clients": result["stuck_clients"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
