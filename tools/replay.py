#!/usr/bin/env python
"""Open-loop trace-replay load harness — realistic load, per-tenant truth.

Every latency/SLO number this repo has published so far came from
closed-loop bench sweeps: N workers, each waiting for its response before
sending the next, a feedback loop that politely backs off exactly when
the server slows down.  Real multi-tenant traffic does the opposite —
arrivals keep coming at their own rate while the server struggles
(coordinated omission is the classic closed-loop lie).  This tool drives
any tpustack LLM server **open-loop**:

- **Arrival process** — per tenant, seeded Gamma-renewal inter-arrival
  times with a ``--burstiness`` knob: 1.0 is Poisson (exponential
  inter-arrivals), >1 is burstier than Poisson (heavy-tailed gaps +
  clumps, CV² = burstiness), <1 is smoother.  The whole schedule is
  derived from ``--seed`` up front, so a replay is reproducible down to
  the request send-times (``schedule_sha`` in the artifact proves two
  runs offered identical load).
- **Length distributions** — lognormal prompt and output lengths
  (``--prompt-chars``/``--new-tokens`` medians + sigmas): heavy-tailed,
  like real traffic, unlike the uniform sweeps.
- **Tenants** — ``--tenants "interactive:4,batch:0.5"`` gives each
  tenant its own rate; every request carries ``X-Tenant-Id``, so the
  server's tenant ledger (``tpustack.obs.accounting``) attributes cost
  and the artifact's per-tenant percentiles can be cross-checked against
  ``GET /debug/tenants``.
- **Shared-prefix pools** — each tenant draws its prompt prefix from a
  small per-tenant pool (``--prefix-pool``), so the radix/block prefix
  cache sees the hit pattern chat traffic actually produces.
- **Goodput** — requests carry ``timeout_s`` (``--deadline-s``); the
  artifact reports ok/shed/deadline/error counts and goodput-vs-offered
  per tenant, the numbers QoS work (ROADMAP item 5) is judged against.

The artifact (one JSON object, ``--out`` or stdout) reports per-tenant
p50/p99 TTFT (server-reported prefill wall — the time-to-first-token a
streaming client would see), TPOT (decode ms/token), and client-side e2e
latency, plus offered vs achieved vs goodput rates.

``--self-host [preset]`` boots an in-process LLM server on an ephemeral
port and replays against it (no cluster needed); ``--tiny`` is the CPU
smoke: tiny model, two tenants at different rates, ~2 s — shelled by
tier-1 and the CI sanitizer job.  Stdlib-only on the client side
(urllib + threads); tpustack is only imported when self-hosting.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import random
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_URL = "http://127.0.0.1:8080"

#: words the synthetic prompts are built from (seeded choice — content
#: matters only in that distinct suffixes must not collide)
_WORDS = ("the", "chip", "wave", "slot", "block", "cache", "queue",
          "tensor", "decode", "prefill", "token", "mesh", "pool", "trace")


# ------------------------------------------------------------- schedule
def parse_tenants(spec: str) -> Dict[str, Dict]:
    """``"a:2,b:0.5:batch"`` → {"a": {"rate": 2.0, "priority": None},
    "b": {"rate": 0.5, "priority": "batch"}}.  The optional third field
    is the QoS priority class every one of that tenant's requests
    carries as ``X-Priority`` (None sends no header — the server's
    per-tenant/policy default applies)."""
    out: Dict[str, Dict] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        if (len(fields) < 2 or len(fields) > 3
                or not fields[0].strip() or not fields[1].strip()):
            raise ValueError(
                f"bad --tenants entry {part!r} (want name:rate[:priority])")
        prio = fields[2].strip().lower() if len(fields) == 3 else None
        if prio is not None and prio not in ("interactive", "batch"):
            raise ValueError(f"bad --tenants entry {part!r}: priority "
                             f"{prio!r} not in (interactive, batch)")
        try:
            rate = float(fields[1])
        except ValueError:
            raise ValueError(f"bad --tenants entry {part!r}: rate "
                             f"{fields[1]!r} is not a number") from None
        out[fields[0].strip()] = {"rate": rate, "priority": prio}
    if not out:
        raise ValueError("--tenants resolved to no tenants")
    return out


def _gamma_interarrivals(rng: random.Random, rate: float, duration: float,
                         burstiness: float) -> List[float]:
    """Arrival times in [0, duration) for one tenant: a Gamma-renewal
    process with mean inter-arrival 1/rate and CV² = burstiness (shape
    k = 1/burstiness, scale = burstiness/rate).  burstiness 1.0 is
    exactly Poisson; >1 clumps arrivals (the bursty, heavy-tailed shape
    open-loop realism is about)."""
    if rate <= 0:
        return []
    k = 1.0 / max(1e-6, burstiness)
    theta = burstiness / rate
    t, out = 0.0, []
    while True:
        t += rng.gammavariate(k, theta)
        if t >= duration:
            return out
        out.append(t)


def _lognormal_int(rng: random.Random, median: float, sigma: float,
                   lo: int, hi: int) -> int:
    return max(lo, min(hi, int(round(
        median * math.exp(rng.gauss(0.0, sigma))))))


def build_schedule(seed: int, tenants: Dict[str, Dict], duration: float,
                   burstiness: float, prompt_chars: float,
                   prompt_sigma: float, new_tokens: float,
                   output_sigma: float, prefix_pool: int,
                   max_new_cap: int = 256) -> List[Dict]:
    """The full offered load, derived from the seed up front (open-loop:
    nothing about the server's behaviour can perturb it).  One dict per
    request: send-time offset, tenant, priority class (None = let the
    server's policy default apply), prompt text, n_predict.  Each tenant
    gets its own child RNG (seeded from (seed, tenant)), so adding a
    tenant never reshuffles another's arrivals."""
    # accept both shapes: {"a": 2.0} (legacy rate-only) and
    # {"a": {"rate": 2.0, "priority": "batch"}} (parse_tenants)
    tenants = {t: (v if isinstance(v, dict)
                   else {"rate": float(v), "priority": None})
               for t, v in tenants.items()}
    requests: List[Dict] = []
    for tenant in sorted(tenants):
        rng = random.Random(f"{seed}:{tenant}")
        pool = []
        for p in range(max(1, prefix_pool)):
            n = _lognormal_int(rng, prompt_chars, prompt_sigma, 4, 4096)
            pool.append(f"[{tenant}/{p}] " + " ".join(
                rng.choice(_WORDS) for _ in range(max(1, n // 5))))
        for i, at in enumerate(_gamma_interarrivals(
                rng, tenants[tenant]["rate"], duration, burstiness)):
            prefix = rng.choice(pool)
            suffix = " ".join(rng.choice(_WORDS) for _ in range(3))
            requests.append({
                "at": round(at, 6),
                "tenant": tenant,
                "priority": tenants[tenant]["priority"],
                "prompt": f"{prefix} q{i}: {suffix}",
                "n_predict": _lognormal_int(rng, new_tokens, output_sigma,
                                            1, max_new_cap),
            })
    requests.sort(key=lambda r: (r["at"], r["tenant"]))
    return requests


def schedule_sha(requests: List[Dict]) -> str:
    """Digest of the offered load — two artifacts with equal shas were
    produced by byte-identical schedules (the reproducibility proof)."""
    blob = json.dumps(requests, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


# -------------------------------------------------------------- driving
def _post_completion(url: str, req: Dict, deadline_s: float,
                     timeout_s: float) -> Dict:
    """One POST /completion; returns the raw result record the reducers
    aggregate.  Every request carries the tenant header (the server-side
    ledger's attribution key) and a per-request deadline when asked."""
    body = {"prompt": req["prompt"], "n_predict": req["n_predict"],
            "temperature": 0}
    if deadline_s > 0:
        body["timeout_s"] = deadline_s
    data = json.dumps(body).encode()
    t0 = time.perf_counter()
    rec = {"tenant": req["tenant"], "at": req["at"], "status": 0,
           "priority": req.get("priority"),
           "e2e_s": None, "ttft_s": None, "tpot_ms": None,
           "tokens": 0}
    try:
        headers = {"Content-Type": "application/json",
                   "X-Tenant-Id": req["tenant"]}
        if req.get("priority"):
            headers["X-Priority"] = req["priority"]
        r = urllib.request.Request(
            url.rstrip("/") + "/completion", data=data,
            headers=headers)
        with urllib.request.urlopen(r, timeout=timeout_s) as resp:
            payload = json.loads(resp.read().decode())
            rec["status"] = resp.status
        rec["e2e_s"] = time.perf_counter() - t0
        timings = payload.get("timings") or {}
        if timings.get("prompt_ms") is not None:
            rec["ttft_s"] = timings["prompt_ms"] / 1e3
        n = timings.get("predicted_n") or 0
        rec["tokens"] = n
        if n and timings.get("predicted_ms"):
            rec["tpot_ms"] = timings["predicted_ms"] / n
    except urllib.error.HTTPError as e:
        rec["status"] = e.code
        rec["e2e_s"] = time.perf_counter() - t0
        e.read()
    except Exception as e:  # connection refused / socket timeout
        rec["status"] = -1
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["e2e_s"] = time.perf_counter() - t0
    return rec


def drive(url: str, requests: List[Dict], deadline_s: float,
          timeout_s: float, log=lambda s: None) -> List[Dict]:
    """Fire the schedule open-loop: each request launches ON TIME on its
    own thread whether or not earlier ones have answered (the whole
    point), and the driver joins them all at the end."""
    results: List[Optional[Dict]] = [None] * len(requests)
    threads = []
    t0 = time.perf_counter()

    def one(i, req):
        results[i] = _post_completion(url, req, deadline_s, timeout_s)

    for i, req in enumerate(requests):
        delay = req["at"] - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=one, args=(i, req), daemon=True)
        th.start()
        threads.append(th)
        if (i + 1) % 50 == 0:
            log(f"offered {i + 1}/{len(requests)}")
    for th in threads:
        th.join(timeout=timeout_s + deadline_s + 30)
    return [r for r in results if r is not None]


# ------------------------------------------------------------ reduction
def _pct(sorted_vals: List[float], q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    rank = q / 100.0 * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (rank - lo)


def _outcome(status: int) -> str:
    if 200 <= status < 400:
        return "ok"
    if status in (429, 503):
        return "shed"
    if status == 504:
        return "deadline"
    return "error"


def _bucket_stats(rs: List[Dict], offered: int, duration: float) -> Dict:
    """Outcome counts + percentiles for one grouping (a tenant or a
    priority class) — the shared reducer body."""
    counts = {"ok": 0, "shed": 0, "deadline": 0, "error": 0}
    for r in rs:
        counts[_outcome(r["status"])] += 1
    finished = sum(counts.values())
    oks = [r for r in rs if _outcome(r["status"]) == "ok"]
    e2e = sorted(r["e2e_s"] for r in oks if r["e2e_s"] is not None)
    ttft = sorted(r["ttft_s"] for r in oks if r["ttft_s"] is not None)
    tpot = sorted(r["tpot_ms"] for r in oks if r["tpot_ms"] is not None)
    return {
        "offered": offered,
        "offered_rps": round(offered / duration, 4),
        "completed": finished,
        **counts,
        "goodput_ratio": (counts["ok"] / finished) if finished else 0.0,
        # same horizon as offered_rps: the ok answers correspond to
        # offers made during `duration`, so dividing by the longer
        # wall (which includes the post-schedule drain tail) would
        # fake a throughput loss even at 100% goodput
        "goodput_rps": round(counts["ok"] / duration, 4),
        "tokens": sum(r["tokens"] for r in oks),
        "ttft_s": {"p50": _pct(ttft, 50), "p99": _pct(ttft, 99)},
        "tpot_ms": {"p50": _pct(tpot, 50), "p99": _pct(tpot, 99)},
        "e2e_s": {"p50": _pct(e2e, 50), "p99": _pct(e2e, 99)},
    }


def reduce_results(requests: List[Dict], results: List[Dict],
                   duration: float, wall_s: float) -> Dict:
    """Per-tenant AND per-priority percentiles + goodput-vs-offered —
    the artifact body.  The ``priorities`` split is how the QoS
    acceptance bar reads: under a saturating batch tenant, interactive
    goodput and tail latency must hold while batch eats the sheds."""
    by_tenant: Dict[str, List[Dict]] = {}
    by_prio: Dict[str, List[Dict]] = {}
    for r in results:
        by_tenant.setdefault(r["tenant"], []).append(r)
        if r.get("priority"):
            by_prio.setdefault(r["priority"], []).append(r)
    offered_by: Dict[str, int] = {}
    offered_prio: Dict[str, int] = {}
    prio_of: Dict[str, Optional[str]] = {}
    for r in requests:
        offered_by[r["tenant"]] = offered_by.get(r["tenant"], 0) + 1
        prio_of[r["tenant"]] = r.get("priority")
        if r.get("priority"):
            offered_prio[r["priority"]] = (
                offered_prio.get(r["priority"], 0) + 1)
    tenants = {}
    for tenant in sorted(offered_by):
        tenants[tenant] = _bucket_stats(by_tenant.get(tenant, []),
                                        offered_by[tenant], duration)
        tenants[tenant]["priority"] = prio_of.get(tenant)
    priorities = {p: _bucket_stats(by_prio.get(p, []), offered_prio[p],
                                   duration)
                  for p in sorted(offered_prio)}
    total_ok = sum(t["ok"] for t in tenants.values())
    total_finished = sum(t["completed"] for t in tenants.values())
    return {
        "tenants": tenants,
        "priorities": priorities,
        "offered": len(requests),
        "offered_rps": round(len(requests) / duration, 4),
        "goodput_rps": round(total_ok / duration, 4),
        "drain_tail_s": round(max(0.0, wall_s - duration), 3),
        "goodput_ratio": (total_ok / total_finished) if total_finished
        else 0.0,
        "shed": sum(t["shed"] for t in tenants.values()),
        "deadline": sum(t["deadline"] for t in tenants.values()),
        "errors": sum(t["error"] for t in tenants.values()),
    }


# ------------------------------------------------------------ self-host
class _SelfHosted:
    """An in-process LLM server on an ephemeral port, driven over real
    HTTP (loopback): the replay exercises the full middleware → queue →
    engine → ledger path without a cluster.  ``tiny`` boots the random-
    weight tiny config (CPU-fast); any other preset defers to the
    environment exactly like the serving entrypoint."""

    def __init__(self, preset: str = "tiny"):
        import asyncio
        import logging

        from tpustack.utils import require_accelerator

        require_accelerator()  # self-hosting computes: same rule as main()
        import jax.numpy as jnp
        from aiohttp import web

        from tpustack.serving.llm_server import LLMServer

        # the serving stack logs to stdout (the kubectl-logs contract);
        # this tool's stdout is the one-line JSON artifact — move the
        # self-hosted server's chatter to stderr
        for h in logging.getLogger("tpustack").handlers:
            if getattr(h, "stream", None) is sys.stdout:
                h.setStream(sys.stderr)

        if preset == "tiny":
            from tpustack.models.llama import LlamaConfig
            from tpustack.models.llm_generate import Generator
            from tpustack.models.text_tokenizer import ByteTokenizer

            gen = Generator(LlamaConfig.tiny(max_seq=128),
                            dtype=jnp.float32, seed=3)
            self.server = LLMServer(generator=gen,
                                    tokenizer=ByteTokenizer(512),
                                    model_name="tiny-replay", max_batch=4)
        else:
            self.server = LLMServer()
        self._loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self.port = None

        def run():
            asyncio.set_event_loop(self._loop)

            async def start():
                runner = web.AppRunner(self.server.build_app())
                await runner.setup()
                site = web.TCPSite(runner, "127.0.0.1", 0)
                await site.start()
                self.port = runner.addresses[0][1]
                self._started.set()
                return runner

            self._runner = self._loop.run_until_complete(start())
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="replay-selfhost")
        self._thread.start()
        if not self._started.wait(timeout=120):
            raise RuntimeError("self-hosted server failed to start")
        self.url = f"http://127.0.0.1:{self.port}"

    def ledger_snapshot(self) -> Dict:
        return self.server.ledger.snapshot()

    def qos_snapshot(self) -> Dict:
        qos = getattr(self.server, "qos", None)
        return qos.snapshot() if qos is not None else {"enabled": False}

    def kvprof_snapshot(self) -> Dict:
        prof = getattr(self.server, "kvprof", None)
        if prof is None:
            return {"enabled": False}
        return dict(prof.snapshot(), enabled=True)

    def close(self):
        import asyncio

        async def stop():
            await self._runner.cleanup()
            self._loop.stop()

        asyncio.run_coroutine_threadsafe(stop(), self._loop)
        self._thread.join(timeout=10)


# ----------------------------------------------------------------- main
def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--url", default=None,
                   help=f"target server (default: TPUSTACK_REPLAY_URL or "
                        f"{DEFAULT_URL})")
    p.add_argument("--autoscaler-url", default="",
                   help="elastic capacity controller base URL; its "
                        "/debug/autoscaler snapshot (desired/actual, "
                        "decisions, scale events) is embedded in the "
                        "artifact as server_autoscaler")
    p.add_argument("--tenants", default="interactive:4,batch:1",
                   help="per-tenant offered load: name:rps[:priority]"
                        "[,...] — the optional priority (interactive|"
                        "batch) rides every request as X-Priority")
    p.add_argument("--duration", type=float, default=10.0,
                   help="seconds of offered load (the schedule horizon)")
    p.add_argument("--seed", type=int, default=0,
                   help="schedule seed — same seed, same offered load, "
                        "byte-identical (schedule_sha)")
    p.add_argument("--burstiness", type=float, default=1.0,
                   help="inter-arrival CV^2: 1=Poisson, >1 bursty "
                        "(Gamma-renewal arrivals)")
    p.add_argument("--prompt-chars", type=float, default=160.0,
                   help="median prompt length, characters (lognormal)")
    p.add_argument("--prompt-sigma", type=float, default=0.6,
                   help="lognormal sigma of the prompt length")
    p.add_argument("--new-tokens", type=float, default=48.0,
                   help="median n_predict (lognormal)")
    p.add_argument("--output-sigma", type=float, default=0.6,
                   help="lognormal sigma of n_predict")
    p.add_argument("--max-new", type=int, default=256,
                   help="hard cap on n_predict")
    p.add_argument("--prefix-pool", type=int, default=4,
                   help="shared prompt prefixes per tenant (exercises the "
                        "radix/block prefix cache)")
    p.add_argument("--deadline-s", type=float, default=60.0,
                   help="per-request timeout_s sent to the server (goodput "
                        "denominator); 0 sends none")
    p.add_argument("--client-timeout-s", type=float, default=300.0,
                   help="client-side socket timeout per request")
    p.add_argument("--self-host", nargs="?", const="env", default=None,
                   metavar="PRESET",
                   help="boot an in-process LLM server and replay against "
                        "it ('tiny' or env-configured)")
    p.add_argument("--tiny", action="store_true",
                   help="CPU smoke: self-host the tiny model with a short, "
                        "small schedule (the tier-1/CI gate)")
    p.add_argument("--host-tier-mb", type=float, default=None,
                   help="self-hosted server's host KV tier arena "
                        "(TPUSTACK_KV_HOST_TIER_MB) — spilled prefix "
                        "blocks land in host RAM and warm revisits "
                        "restore instead of recomputing; the artifact's "
                        "server_kvcache snapshot then carries the "
                        "host_tier ledger + capacity what-if point")
    p.add_argument("--qos-policy", default="",
                   help="TPUSTACK_QOS_POLICY for the self-hosted server "
                        "(inline JSON or a file path): per-tenant "
                        "priority defaults + token-bucket quotas")
    p.add_argument("--env", action="append", default=[], metavar="K=V",
                   help="extra env for the self-hosted server (e.g. "
                        "TPUSTACK_MAX_QUEUE_DEPTH=4); repeatable, applied "
                        "before the server module is imported")
    p.add_argument("--assert-qos", action="store_true",
                   help="exit 3 unless interactive goodput_ratio >= batch "
                        "goodput_ratio AND the self-hosted server shed at "
                        "least one batch request (the CI mixed-priority "
                        "smoke gate)")
    p.add_argument("--out", default="",
                   help="write the JSON artifact here (default: stdout)")
    args = p.parse_args(argv)

    log = lambda s: print(f"[replay] {s}", file=sys.stderr, flush=True)

    if args.tiny:
        # CPU smoke shape: ~8 requests whose worst-case block footprint
        # fits the tiny server's pool simultaneously (admission is
        # allocation — queued requests hold blocks), so both tenants
        # complete work and the per-tenant percentiles are real numbers;
        # shed/deadline paths are exercised by the dedicated tests, not
        # by starving the smoke
        args.self_host = args.self_host or "tiny"
        args.duration = min(args.duration, 2.0)
        args.tenants = ("interactive:3,batch:1"
                        if args.tenants == "interactive:4,batch:1"
                        else args.tenants)
        args.prompt_chars = min(args.prompt_chars, 24.0)
        args.new_tokens = min(args.new_tokens, 4.0)
        args.max_new = min(args.max_new, 8)
        args.deadline_s = min(args.deadline_s, 60.0)
        # host KV tier ON for the smoke (tiny arena, crossover guard off
        # — on CPU both of its EMAs measure dispatch noise): kv_report
        # --tiny renders this run's server_kvcache, so the host_tier
        # capacity point and spill/restore ledger get CI coverage.  An
        # explicit --host-tier-mb (even 0) wins
        if args.host_tier_mb is None:
            args.host_tier_mb = 8.0
            os.environ.setdefault("TPUSTACK_KV_HOST_TIER_CROSSOVER", "0")

    # self-hosted server env: QoS policy + ad-hoc knobs land in
    # os.environ BEFORE the server is imported/constructed (the knob
    # registry reads at construction time)
    for kv in args.env:
        k, sep, v = kv.partition("=")
        if not sep:
            p.error(f"--env {kv!r}: want K=V")
        os.environ[k] = v
    if args.qos_policy:
        os.environ["TPUSTACK_QOS_POLICY"] = args.qos_policy
    if args.host_tier_mb is not None:
        os.environ["TPUSTACK_KV_HOST_TIER_MB"] = str(args.host_tier_mb)

    tenants = parse_tenants(args.tenants)
    schedule = build_schedule(
        args.seed, tenants, args.duration, args.burstiness,
        args.prompt_chars, args.prompt_sigma, args.new_tokens,
        args.output_sigma, args.prefix_pool, max_new_cap=args.max_new)
    sha = schedule_sha(schedule)
    log(f"schedule: {len(schedule)} requests over {args.duration}s from "
        f"seed {args.seed} (sha {sha}), tenants "
        + ", ".join(f"{t}@{c['rate']}rps"
                    + (f"/{c['priority']}" if c["priority"] else "")
                    for t, c in sorted(tenants.items())))
    if not schedule:
        print(json.dumps({"error": "empty schedule (rates x duration "
                          "produced no arrivals)"}))
        return 2

    host = None
    url = args.url
    if url is None:
        try:
            from tpustack.utils import knobs as _knobs

            url = _knobs.get_str("TPUSTACK_REPLAY_URL") or DEFAULT_URL
        except ImportError:
            url = DEFAULT_URL
    try:
        if args.self_host:
            preset = "tiny" if args.self_host == "tiny" else "env"
            log(f"self-hosting LLM server (preset={preset})")
            host = _SelfHosted(preset)
            url = host.url
        t0 = time.perf_counter()
        results = drive(url, schedule, args.deadline_s,
                        args.client_timeout_s, log=log)
        wall_s = time.perf_counter() - t0
        artifact = {
            "metric": "replay_open_loop",
            "unit": "per-tenant goodput + latency percentiles",
            "url": url,
            "seed": args.seed,
            "schedule_sha": sha,
            "config": {
                "tenants": tenants, "duration_s": args.duration,
                "burstiness": args.burstiness,
                "prompt_chars_median": args.prompt_chars,
                "prompt_sigma": args.prompt_sigma,
                "new_tokens_median": args.new_tokens,
                "output_sigma": args.output_sigma,
                "prefix_pool": args.prefix_pool,
                "deadline_s": args.deadline_s,
            },
            "wall_s": round(wall_s, 3),
            **reduce_results(schedule, results, args.duration, wall_s),
        }
        artifact["value"] = artifact["goodput_rps"]
        # when --url points at the L7 router (tpustack.serving.router),
        # its /debug/router snapshot rides along: backend health/circuit
        # states plus failover and prefix-affinity counters — the
        # scale-out run's server-side evidence
        try:
            with urllib.request.urlopen(
                    url.rstrip("/") + "/debug/router", timeout=5) as r:
                artifact["server_router"] = json.loads(r.read().decode())
        except Exception:
            log("no /debug/router on target (driving a backend directly)")
        if args.autoscaler_url:
            # the elastic run's control-plane evidence: what the capacity
            # controller saw and did while this load was offered
            try:
                with urllib.request.urlopen(
                        args.autoscaler_url.rstrip("/") +
                        "/debug/autoscaler", timeout=5) as r:
                    artifact["server_autoscaler"] = json.loads(
                        r.read().decode())
            except Exception as exc:
                log(f"autoscaler snapshot failed: {exc}")
        if host is not None:
            # the server-side ledger view of the same run — what the
            # conservation tests cross-check the client artifact against
            artifact["server_tenants"] = host.ledger_snapshot()
            # ... and the QoS policy's own counters/buckets (shed,
            # preempt, quota_throttle per priority) — the smoke gate's
            # "shed landed on batch" evidence
            artifact["server_qos"] = host.qos_snapshot()
            # ... and the KV working-set observatory's snapshot (miss-
            # ratio curve, working set, calibration) — what kv_report.py
            # renders a capacity recommendation from
            artifact["server_kvcache"] = host.kvprof_snapshot()
    finally:
        if host is not None:
            host.close()

    blob = json.dumps(artifact)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob + "\n")
        log(f"artifact written to {args.out}")
    print(blob)

    if args.assert_qos:
        prios = artifact.get("priorities") or {}
        inter = (prios.get("interactive") or {}).get("goodput_ratio")
        batch = (prios.get("batch") or {}).get("goodput_ratio")
        counters = (artifact.get("server_qos") or {}).get("counters") or {}
        batch_shed = (counters.get("shed", {}).get("batch", 0)
                      + counters.get("quota_throttle", {}).get("batch", 0))
        problems = []
        if inter is None or batch is None:
            problems.append("need both an interactive and a batch tenant "
                            "(--tenants name:rps:priority)")
        elif inter < batch:
            problems.append(f"interactive goodput {inter:.3f} < batch "
                            f"goodput {batch:.3f}")
        if batch_shed == 0:
            problems.append("no batch request was shed/throttled "
                            "(qos_shed{priority='batch'} == 0) — the "
                            "smoke did not saturate, or QoS is off")
        if problems:
            for msg in problems:
                log(f"--assert-qos FAILED: {msg}")
            return 3
        log(f"--assert-qos ok: interactive {inter:.3f} >= batch "
            f"{batch:.3f}, batch sheds {batch_shed}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
