#!/usr/bin/env python3
"""Replica-kill chaos drill for the routed serving path.

Boots N self-hosted tiny llm_server replicas and the L7 router
(``tpustack.serving.router``) in subprocesses, drives a mixed-priority
multi-tenant ``replay`` schedule THROUGH the router, then — mid-load —
SIGKILLs one replica and SIGTERM-drains another, and asserts the
resilience bar end to end:

- per-tenant interactive goodput >= threshold (default 0.9): the router
  re-rendezvoused around the dead replica and retried the spills;
- failed in-flight requests <= the killed replica's slot count: only
  work that was physically on the murdered pod may be lost, and most of
  THAT comes back through the router's connect-error failover;
- affinity kept working: repeat prefixes still hit (the kill shows up
  as cold moves, not a routing collapse), and at least one failover was
  actually exercised;
- zero KV-pool leaks on survivors (``tpustack_llm_kv_used_blocks`` == 0
  once quiesced) and zero sanitizer violations anywhere — the replicas
  and the router run under ``TPUSTACK_SANITIZE=1``;
- the fleet watchtower (``tpustack.serving.watchtower``, booted
  alongside the router) produced an incident bundle for the SIGKILL
  that names the killed replica in its ejection events, holds a
  stitched trace spanning router and replica processes plus burn-rate
  alert state and per-process flight snapshots, and renders to
  markdown via ``tools/incident_report.py``.

``--fast`` is the tier-1/CI shape: 2 replicas, SIGKILL one mid-load,
SIGTERM-drain the other after the last request is offered (the drain
covers the in-flight tail).  The full drill uses 3 replicas and lands
BOTH kills mid-load.

Exit codes: 0 all asserts pass, 1 an assert failed (diagnostics on
stderr, artifact on stdout), 2 boot/usage failure.
"""

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.replay import (_outcome, build_schedule, drive,  # noqa: E402
                          parse_tenants, reduce_results, schedule_sha)

#: the tiny replica's engine slots — the in-flight-loss bound
REPLICA_SLOTS = 4


def _log(msg: str) -> None:
    print(f"chaos_serving: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ subprocesses
def serve_replica(port: int) -> None:
    """``--serve-replica`` entry: one tiny llm_server on ``port`` with the
    real SIGTERM drain installed (the thing the chaos drill kills)."""
    import jax.numpy as jnp
    from aiohttp import web

    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_generate import Generator
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer
    from tpustack.utils import enable_compile_cache

    enable_compile_cache()  # replicas share the tiny model's XLA cache
    gen = Generator(LlamaConfig.tiny(max_seq=512), dtype=jnp.float32, seed=3)
    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="tiny-chaos", max_batch=REPLICA_SLOTS)
    server.resilience.install_signal_handlers()
    web.run_app(server.build_app(), host="127.0.0.1", port=port,
                access_log=None, handle_signals=False)


def _free_ports(n: int):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _http_json(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _warmup(urls, log=_log) -> None:
    """Trigger each replica's XLA compiles BEFORE the clock starts: the
    drill measures failover behaviour, not first-compile latency, and an
    open-loop schedule aimed at a still-compiling replica just measures
    the admission queue overflowing."""
    def _fire(url, chars, n_predict):
        req = urllib.request.Request(
            url + "/completion",
            data=json.dumps({"prompt": "w" * chars,
                             "n_predict": n_predict}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            r.read()

    for url in urls:
        # one prompt per prefill bucket the schedule can hit (byte
        # tokenizer: chars ~ tokens; buckets are powers of two) ...
        t0 = time.monotonic()
        for chars in (50, 100, 200, 400):
            _fire(url, chars, 4)
        # ... then concurrent rounds so the continuous engine compiles
        # its decode step at every batch size it can reach mid-drill
        for k in (2, 3, REPLICA_SLOTS):
            threads = [threading.Thread(target=_fire,
                                        args=(url, 90 + 30 * j, 16))
                       for j in range(k)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        log(f"warmed {url} (4 prefill buckets, batch 1-"
            f"{REPLICA_SLOTS} decode) in {time.monotonic() - t0:.1f}s")


def _wait_ready(url: str, deadline_s: float, what: str) -> bool:
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        try:
            with urllib.request.urlopen(url + "/readyz", timeout=2) as r:
                if r.status == 200:
                    return True
        except Exception:
            pass
        time.sleep(0.25)
    _log(f"{what} not ready after {deadline_s:.0f}s")
    return False


_METRIC_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def _scrape_sum(url: str, metric: str) -> float:
    """Sum of every sample of ``metric`` in the target's /metrics text."""
    total, found = 0.0, False
    with urllib.request.urlopen(url + "/metrics", timeout=5) as r:
        for line in r.read().decode().splitlines():
            m = _METRIC_RE.match(line)
            if m and m.group(1) == metric:
                total += float(m.group(3))
                found = True
    return total if found else 0.0


# ------------------------------------------------------------------- drill
def run_drill(args) -> int:
    n = args.replicas
    ports = _free_ports(n + 2)
    replica_ports, router_port, watch_port = ports[:n], ports[n], ports[n + 1]
    replica_urls = [f"http://127.0.0.1:{p}" for p in replica_ports]
    router_url = f"http://127.0.0.1:{router_port}"
    watch_url = f"http://127.0.0.1:{watch_port}"

    # the drill starts several replicas on this host and pins none to a
    # chip (one process per chip: ROADMAP D7/R10) — CPU replicas only
    base_env = dict(os.environ,
                    JAX_PLATFORMS="cpu",
                    TPUSTACK_SANITIZE="1",
                    TPUSTACK_SANITIZE_MODE="report",
                    TPUSTACK_METRICS_PORT="0",
                    # quiesce contract: with the prefix cache off, a
                    # drained pool MUST be at 0 used blocks — any
                    # remainder is a leaked refcount
                    TPUSTACK_PREFIX_CACHE="0",
                    # headroom over the auto (dense-parity) sizing: after
                    # the SIGKILL the lone survivor absorbs the WHOLE
                    # failover surge, and on a loaded CI box its decode
                    # rate drops — without the extra blocks the drill
                    # measures pool exhaustion, not failover behaviour
                    TPUSTACK_KV_POOL_BLOCKS="96",
                    TPUSTACK_DRAIN_TIMEOUT_S="20")
    router_env = dict(base_env,
                      PORT=str(router_port),
                      TPUSTACK_ROUTER_BACKENDS=",".join(replica_urls),
                      TPUSTACK_ROUTER_HEALTH_INTERVAL_S="0.3",
                      TPUSTACK_ROUTER_EJECT_AFTER="2",
                      TPUSTACK_ROUTER_HALF_OPEN_S="2.0",
                      TPUSTACK_ROUTER_RETRY_BUDGET="3",
                      TPUSTACK_ROUTER_RETRY_JITTER_S="0.02",
                      # block-align affinity keys well below the prompt
                      # median so the per-tenant prefix pools repeat
                      TPUSTACK_ROUTER_AFFINITY_CHUNK="64")

    logdir = tempfile.mkdtemp(prefix="chaos-serving-")
    procs, logfiles = {}, {}

    def _spawn(name, argv, env):
        slug = re.sub(r"[^A-Za-z0-9._-]+", "_", name)
        logfiles[name] = os.path.join(logdir, f"{slug}.log")
        out = open(logfiles[name], "w")
        procs[name] = subprocess.Popen(argv, env=env, cwd=REPO,
                                       stdout=out, stderr=subprocess.STDOUT)
        out.close()

    def _log_tail(name, lines=15):
        try:
            with open(logfiles[name]) as f:
                tail = f.read().splitlines()[-lines:]
            for ln in tail:
                _log(f"  [{name}] {ln}")
        except OSError:
            pass

    try:
        for url, port in zip(replica_urls, replica_ports):
            _spawn(url, [sys.executable, os.path.abspath(__file__),
                         "--serve-replica", "--port", str(port)], base_env)
        _log(f"booting {n} replicas on {replica_ports} (logs: {logdir})")
        for url in replica_urls:
            if not _wait_ready(url, 180, f"replica {url}"):
                _log_tail(url)
                return 2
        _spawn("router", [sys.executable, "-m", "tpustack.serving.router"],
               router_env)
        if not _wait_ready(router_url, 30, "router"):
            _log_tail("router")
            return 2
        _log(f"router up on {router_port} -> {len(replica_urls)} backends")

        # the fleet watchtower rides along: it must turn the SIGKILL's
        # ejection into an incident bundle whose stitched trace spans
        # router and replica processes (asserted below)
        watchtower_env = dict(
            base_env,
            PORT=str(watch_port),
            TPUSTACK_WATCHTOWER_ROUTER_URL=router_url,
            # quick enough to catch the ejection warm, slow enough that
            # fleet-wide scraping doesn't steal CPU from the drill itself
            TPUSTACK_WATCHTOWER_INTERVAL_S="0.5",
            TPUSTACK_WATCHTOWER_INCIDENT_COOLDOWN_S="5",
            TPUSTACK_WATCHTOWER_INCIDENT_DIR=os.path.join(
                logdir, "incidents"))
        _spawn("watchtower",
               [sys.executable, "-m", "tpustack.serving.watchtower"],
               watchtower_env)
        if not _wait_ready(watch_url, 30, "watchtower"):
            _log_tail("watchtower")
            return 2
        _log(f"watchtower up on {watch_port} (watching {router_url})")

        tenants = parse_tenants(args.tenants)
        schedule = build_schedule(
            args.seed, tenants, args.duration, burstiness=1.2,
            prompt_chars=120.0, prompt_sigma=0.4, new_tokens=6.0,
            output_sigma=0.4, prefix_pool=3, max_new_cap=8)
        sha = schedule_sha(schedule)
        _log(f"schedule: {len(schedule)} requests over {args.duration}s "
             f"(sha {sha})")

        _warmup(replica_urls)

        # victims: the SIGKILL lands on the first replica, the SIGTERM
        # drain on the second; survivors = the rest (+ the router).  In
        # --fast mode (2 replicas = no survivors mid-load) the drain is
        # sent AFTER the schedule finishes, so the load always has a
        # healthy backend; the full drill drains mid-load.
        kill_url, drain_url = replica_urls[0], replica_urls[1]
        kill_at = args.duration * 0.35
        timers = [
            threading.Timer(kill_at, lambda: (
                _log(f"SIGKILL {kill_url}"),
                procs[kill_url].send_signal(signal.SIGKILL))),
        ]
        drain_at = args.duration * 0.65
        if not args.fast:
            timers.append(threading.Timer(drain_at, lambda: (
                _log(f"SIGTERM (drain) {drain_url}"),
                procs[drain_url].send_signal(signal.SIGTERM))))

        for t in timers:
            t.daemon = True
            t.start()

        t0 = time.perf_counter()
        results = drive(router_url, schedule, deadline_s=30.0,
                        timeout_s=60.0, log=_log)
        wall_s = time.perf_counter() - t0
        summary = reduce_results(schedule, results, args.duration, wall_s)
        for t in timers:
            t.cancel()
        if args.fast:
            drain_at = wall_s
            _log(f"SIGTERM (drain) {drain_url}")
            procs[drain_url].send_signal(signal.SIGTERM)

        failed = [r for r in results
                  if r and _outcome(r["status"]) == "error"]
        for r in failed[:5]:
            _log(f"failed request: status={r['status']} "
                 f"err={r.get('error', '-')!r}")

        router_debug = _http_json(router_url + "/debug/router")

        # the drained replica must finish its in-flight tail and exit 0
        # on its own (that IS the drain contract); the SIGKILLed one is
        # simply dead.  Everything else is a survivor: quiesce it and
        # read the leak/violation counters.
        drain_exit = None
        try:
            drain_exit = procs[drain_url].wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        survivors = [u for u in replica_urls
                     if u not in (kill_url, drain_url)]
        survivor_stats = {}
        leak, violations = {}, {}
        for url in survivors:
            # quiesce: all slots freed -> the paged pool must be back at
            # zero used blocks (the prefix cache is off)
            used = None
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                used = _scrape_sum(url, "tpustack_llm_kv_used_blocks")
                if used == 0:
                    break
                time.sleep(0.5)
            leak[url] = used
            violations[url] = _scrape_sum(
                url, "tpustack_sanitizer_violations_total")
            survivor_stats[url] = {"kv_used_blocks": used,
                                   "sanitizer_violations": violations[url]}
        violations["router"] = _scrape_sum(
            router_url, "tpustack_sanitizer_violations_total")
        violations["watchtower"] = _scrape_sum(
            watch_url, "tpustack_sanitizer_violations_total")

        # the watchtower must have turned the SIGKILL into an incident
        # bundle; give it a few ticks' grace past the drill's end
        bundle, bundle_summary = None, None
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            listing = _http_json(watch_url + "/debug/incidents")["incidents"]
            if listing:
                bundle_summary = listing[-1]  # oldest = the kill's bundle
                bundle = _http_json(
                    watch_url + f"/debug/incidents/{bundle_summary['id']}")
                break
            time.sleep(0.5)

        # ------------------------------------------------------- asserts
        problems = []
        for tenant, stats in summary["tenants"].items():
            if stats.get("priority") == "interactive" \
                    and stats["goodput_ratio"] < args.goodput:
                problems.append(
                    f"tenant {tenant} goodput {stats['goodput_ratio']:.3f}"
                    f" < {args.goodput}")
        if summary["errors"] > REPLICA_SLOTS:
            problems.append(
                f"{summary['errors']} failed in-flight requests > the "
                f"killed replica's {REPLICA_SLOTS} slots")
        aff = router_debug.get("affinity") or {}
        if not aff.get("hit"):
            problems.append("no affinity hits — repeat prefixes never "
                            "landed on a warm replica")
        if not router_debug.get("failovers"):
            problems.append("no failovers recorded — the kill was never "
                            "routed around")
        if drain_exit is None:
            problems.append(f"drained replica {drain_url} did not exit "
                            "within its drain window")
        elif drain_exit != 0:
            problems.append(f"drained replica {drain_url} exited "
                            f"{drain_exit}, want 0 (clean drain)")
        for who, v in violations.items():
            if v:
                problems.append(f"{who}: {v:.0f} sanitizer violations")
        for url, used in leak.items():
            if used:
                problems.append(f"{url}: {used:.0f} KV blocks still in "
                                "use after quiesce (pool leak)")

        watchtower_stats = {"incidents": 0}
        if bundle is None:
            problems.append("watchtower produced no incident bundle for "
                            "the SIGKILL")
        else:
            listing = _http_json(watch_url + "/debug/incidents")["incidents"]
            watchtower_stats["incidents"] = len(listing)
            watchtower_stats["bundle"] = {
                "id": bundle["id"], "reason": bundle["reason"],
                "n_traces": len(bundle.get("traces") or ())}
            events = (bundle.get("router") or {}).get("events") or []
            if not any(e.get("kind") == "ejection"
                       and e.get("url") == kill_url for e in events):
                problems.append(
                    f"incident bundle {bundle['id']} does not name the "
                    f"killed replica {kill_url} in its ejection events")
            stitched = [t for t in bundle.get("traces") or ()
                        if len(t.get("processes") or ()) >= 2]
            if not stitched:
                problems.append(
                    f"incident bundle {bundle['id']} holds no stitched "
                    "trace spanning router and replica processes")
            else:
                watchtower_stats["bundle"]["stitched_processes"] = \
                    stitched[0]["processes"]
            if "rules" not in (bundle.get("alerts") or {}):
                problems.append(f"incident bundle {bundle['id']} carries "
                                "no burn-rate alert state")
            flight = bundle.get("flight") or {}
            if "router" not in flight or not any(
                    p.startswith("replica@") for p in flight):
                problems.append(f"incident bundle {bundle['id']} is "
                                "missing per-process flight snapshots")
            # the forensics path end to end: the report tool must render
            # this bundle to markdown without error
            try:
                from tools.incident_report import render
                md = render(bundle)
                if kill_url not in md:
                    problems.append("incident_report markdown does not "
                                    f"mention the killed replica "
                                    f"{kill_url}")
                watchtower_stats["bundle"]["report_chars"] = len(md)
            except Exception as e:
                problems.append(f"incident_report failed to render "
                                f"bundle {bundle['id']}: {e!r}")

        artifact = {
            "metric": "chaos_serving",
            "fast": bool(args.fast),
            "replicas": n,
            "seed": args.seed,
            "schedule_sha": sha,
            "duration_s": args.duration,
            "wall_s": round(wall_s, 3),
            "kill": {"sigkill": kill_url, "sigkill_at_s": round(kill_at, 2),
                     "sigterm": drain_url,
                     "sigterm_at_s": round(drain_at, 2),
                     "drain_exit": drain_exit},
            "summary": summary,
            "server_router": {
                "backends": router_debug.get("backends"),
                "requests": router_debug.get("requests"),
                "failovers": router_debug.get("failovers"),
                "affinity": aff,
            },
            "survivors": survivor_stats,
            "watchtower": watchtower_stats,
            "router_sanitizer_violations": violations["router"],
            "problems": problems,
            "ok": not problems,
        }
        blob = json.dumps(artifact)
        if args.out:
            with open(args.out, "w") as f:
                f.write(blob + "\n")
            _log(f"artifact written to {args.out}")
        print(blob)

        if problems:
            for msg in problems:
                _log(f"ASSERT FAILED: {msg}")
            _log_tail("router")
            return 1
        _log(f"ok: goodput held through SIGKILL+drain "
             f"(ratio {summary['goodput_ratio']:.3f}, "
             f"{sum((router_debug.get('failovers') or {}).values())} "
             f"failovers, affinity hit ratio "
             f"{aff.get('hit_ratio')})")
        return 0
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
        for proc in procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--fast", action="store_true",
                   help="tier-1/CI shape: 2 replicas, short schedule, "
                        "SIGTERM after the last offer")
    p.add_argument("--replicas", type=int, default=None,
                   help="replica count (default: 3, --fast: 2)")
    p.add_argument("--duration", type=float, default=None,
                   help="schedule horizon seconds (default: 12, --fast: 6)")
    p.add_argument("--tenants", default="interactive:5:interactive,"
                                        "batch:2:batch",
                   help="replay tenant spec (name:rps:priority,...)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--goodput", type=float, default=0.9,
                   help="per-interactive-tenant goodput_ratio floor")
    p.add_argument("--out", default="", help="write the JSON artifact here")
    p.add_argument("--serve-replica", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.serve_replica:
        if not args.port:
            p.error("--serve-replica needs --port")
        serve_replica(args.port)
        return 0

    args.replicas = args.replicas or (2 if args.fast else 3)
    args.duration = args.duration or (6.0 if args.fast else 12.0)
    if args.replicas < 2:
        p.error("need at least 2 replicas (one to kill, one to survive)")
    return run_drill(args)


if __name__ == "__main__":
    sys.exit(main())
