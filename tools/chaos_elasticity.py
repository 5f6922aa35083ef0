#!/usr/bin/env python3
"""Elasticity chaos drill: the autoscaler under a bursty load swing.

Boots a minimal routed fleet (tiny ``llm_server`` replicas + the L7
router, all under ``TPUSTACK_SANITIZE=1``), runs the REAL autoscaler
in-process with its :class:`LocalSubprocessExecutor`, and drives a
three-phase replay — quiet → surge → quiet — THROUGH the router,
asserting the elastic-capacity bar end to end:

- the fleet GROWS during the surge (an ``up`` scale event fires inside
  the surge window) and shrinks back to the floor after it;
- per-tenant interactive goodput >= threshold (default 0.9) in EVERY
  phase — scaling is invisible to clients;
- zero in-flight loss at every scale event: no request errors anywhere
  in the run (scale-up registers replicas only once ready; scale-down
  drains before terminating);
- scale-down only drains the idle-most replica: the victim's affinity
  ledger share is the fleet minimum at decision time, its in-flight
  count is zero when it is terminated, and it exits 0 through the real
  SIGTERM drain state machine;
- no flapping: at most one scale-direction change per load phase;
- zero KV-pool leaks on survivors once quiesced, zero sanitizer
  violations on survivors and the router.

``--fast`` is the tier-1/CI shape (1 replica floor, 2 ceiling, short
phases).  Exit codes: 0 all asserts pass, 1 an assert failed
(diagnostics on stderr, artifact on stdout), 2 boot/usage failure.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.chaos_serving import (REPLICA_SLOTS, _free_ports,  # noqa: E402
                                 _http_json, _scrape_sum, _wait_ready,
                                 _warmup)
from tools.replay import (build_schedule, drive,  # noqa: E402
                          parse_tenants, reduce_results, schedule_sha)

ADMIN_TOKEN = "chaos-elasticity-admin"


def _log(msg: str) -> None:
    print(f"chaos_elasticity: {msg}", file=sys.stderr, flush=True)


def _phase_events(events, t0, t1):
    return [e for e in events if t0 <= e.get("t", 0) < t1]


def _direction_changes(events) -> int:
    dirs = [e["direction"] for e in events
            if e.get("direction") in ("up", "down")]
    return sum(1 for a, b in zip(dirs, dirs[1:]) if a != b)


# ------------------------------------------------------------------- drill
def run_drill(args) -> int:
    from tpustack.obs.metrics import Registry
    from tpustack.serving.autoscaler import (Autoscaler,
                                             LocalSubprocessExecutor)

    (router_port,) = _free_ports(1)
    router_url = f"http://127.0.0.1:{router_port}"
    logdir = tempfile.mkdtemp(prefix="chaos-elasticity-")
    registry_file = os.path.join(logdir, "backends.txt")
    with open(registry_file, "w"):
        pass

    # the executor starts several replicas on this host and pins none to
    # a chip (one process per chip: ROADMAP D7/R10) — CPU replicas only
    base_env = dict(os.environ,
                    JAX_PLATFORMS="cpu",
                    TPUSTACK_SANITIZE="1",
                    TPUSTACK_SANITIZE_MODE="report",
                    TPUSTACK_METRICS_PORT="0",
                    # quiesce contract: prefix cache off -> a drained pool
                    # must read 0 used blocks (any remainder is a leak)
                    TPUSTACK_PREFIX_CACHE="0",
                    TPUSTACK_KV_POOL_BLOCKS="96",
                    TPUSTACK_DRAIN_TIMEOUT_S="20",
                    TPUSTACK_ADMIN_TOKEN=ADMIN_TOKEN)
    router_env = dict(base_env,
                      PORT=str(router_port),
                      TPUSTACK_ROUTER_BACKENDS="@" + registry_file,
                      TPUSTACK_ROUTER_HEALTH_INTERVAL_S="0.3",
                      TPUSTACK_ROUTER_EJECT_AFTER="2",
                      TPUSTACK_ROUTER_HALF_OPEN_S="2.0",
                      TPUSTACK_ROUTER_RETRY_BUDGET="3",
                      TPUSTACK_ROUTER_RETRY_JITTER_S="0.02",
                      TPUSTACK_ROUTER_AFFINITY_CHUNK="64")
    scaler_env = {
        "TPUSTACK_AUTOSCALER_MIN": str(args.min_replicas),
        "TPUSTACK_AUTOSCALER_MAX": str(args.max_replicas),
        "TPUSTACK_AUTOSCALER_TARGET_LOAD": str(args.target_load),
        "TPUSTACK_AUTOSCALER_HYSTERESIS": "0.25",
        "TPUSTACK_AUTOSCALER_INTERVAL_S": "0.5",
        "TPUSTACK_AUTOSCALER_UP_COOLDOWN_S": "2.0",
        "TPUSTACK_AUTOSCALER_DOWN_COOLDOWN_S": str(args.down_cooldown),
        "TPUSTACK_AUTOSCALER_DOWN_STABLE_TICKS": "3",
        "TPUSTACK_AUTOSCALER_KV_FREE_MIN": "0.02",
    }

    def spawn(port: int):
        return [sys.executable,
                os.path.join(REPO, "tools", "chaos_serving.py"),
                "--serve-replica", "--port", str(port)]

    executor = LocalSubprocessExecutor(
        registry_file, spawn, env=base_env, cwd=REPO,
        admin_token=ADMIN_TOKEN, log_dir=logdir,
        ready_timeout_s=240.0, drain_timeout_s=60.0)
    scaler = None
    router_proc = None
    router_logfile = os.path.join(logdir, "router.log")

    def _router_log_tail(lines=15):
        try:
            with open(router_logfile) as f:
                for ln in f.read().splitlines()[-lines:]:
                    _log(f"  [router] {ln}")
        except OSError:
            pass

    try:
        # ---- boot the floor fleet, then the router over the @file registry
        _log(f"booting {args.min_replicas} floor replica(s) "
             f"(logs: {logdir})")
        boot_events = executor.scale_to(args.min_replicas, [])
        if not all(e.get("ready") for e in boot_events):
            _log(f"floor replica boot failed: {boot_events}")
            return 2
        out = open(router_logfile, "w")
        router_proc = subprocess.Popen(
            [sys.executable, "-m", "tpustack.serving.router"],
            env=router_env, cwd=REPO, stdout=out, stderr=subprocess.STDOUT)
        out.close()
        if not _wait_ready(router_url, 30, "router"):
            _router_log_tail()
            return 2
        _log(f"router up on {router_port} -> {executor.urls()}")
        _warmup(executor.urls(), log=_log)

        scaler = Autoscaler(router_url, executor,
                            registry=Registry(), env=scaler_env)
        scaler.start()

        # ---- the three load phases.  Between phases we wait for the
        # controller to converge (desired == actual, no scale in flight)
        # so each phase's events — including a scale-up whose replica is
        # still compiling when the phase's offers stop — land inside
        # that phase's window for the flap accounting.
        phase_specs = [
            ("quiet", args.quiet_duration, args.quiet_tenants),
            ("surge", args.surge_duration, args.surge_tenants),
            ("quiet2", args.quiet_duration, args.quiet_tenants),
        ]
        phases = []
        for i, (name, duration, tenants_spec) in enumerate(phase_specs):
            tenants = parse_tenants(tenants_spec)
            schedule = build_schedule(
                args.seed + i, tenants, duration, burstiness=1.2,
                prompt_chars=120.0, prompt_sigma=0.4, new_tokens=6.0,
                output_sigma=0.4, prefix_pool=3, max_new_cap=8)
            t0 = time.time()
            _log(f"phase {name}: {len(schedule)} requests over "
                 f"{duration}s (sha {schedule_sha(schedule)})")
            wall0 = time.perf_counter()
            results = drive(router_url, schedule, deadline_s=30.0,
                            timeout_s=60.0, log=_log)
            wall_s = time.perf_counter() - wall0
            summary = reduce_results(schedule, results, duration, wall_s)
            # convergence barrier: a scale decision made during this
            # phase finishes executing before the next phase starts
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                dbg = scaler.debug_payload()
                if dbg["converged"]:
                    break
                time.sleep(0.5)
            phases.append({"name": name, "t0": t0, "t1": time.time(),
                           "duration_s": duration, "wall_s": round(wall_s, 3),
                           "offered": len(schedule), "summary": summary,
                           "actual_after": executor.actual()})
            _log(f"phase {name} done: goodput "
                 f"{summary['goodput_ratio']:.3f}, errors "
                 f"{summary['errors']}, fleet now {executor.actual()}")

        # ---- settle: the idle fleet must give the surge capacity back
        settle_deadline = time.monotonic() + args.settle_timeout
        while time.monotonic() < settle_deadline:
            if (executor.actual() == args.min_replicas
                    and scaler.debug_payload()["converged"]):
                break
            time.sleep(0.5)
        phases[-1]["t1"] = time.time()  # settle belongs to the last phase
        scaler.close()
        scaler_debug = scaler.debug_payload()
        events = scaler_debug["events"]
        final_actual = executor.actual()

        # ---- quiesce + leak/violation counters on the surviving fleet
        survivors = executor.urls()
        survivor_stats, leak, violations = {}, {}, {}
        for url in survivors:
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
        router_debug = _http_json(router_url + "/debug/router")

        # ------------------------------------------------------- asserts
        problems = []
        surge = next(p for p in phases if p["name"] == "surge")
        ups = [e for e in events if e["direction"] == "up"]
        downs = [e for e in events if e["direction"] == "down"]
        surge_ups = _phase_events(ups, surge["t0"], surge["t1"])
        if not surge_ups:
            problems.append("fleet never grew during the surge (no up "
                            "scale event in the surge window)")
        if not all(e.get("ready") for e in surge_ups):
            problems.append(f"a surge scale-up replica never became "
                            f"ready: {surge_ups}")
        if not downs:
            problems.append("fleet never scaled back down after the surge")
        if final_actual != args.min_replicas:
            problems.append(f"fleet did not settle at the floor: "
                            f"{final_actual} != {args.min_replicas}")
        for p in phases:
            for tenant, stats in p["summary"]["tenants"].items():
                if stats.get("priority") == "interactive" \
                        and stats["goodput_ratio"] < args.goodput:
                    problems.append(
                        f"phase {p['name']}: tenant {tenant} goodput "
                        f"{stats['goodput_ratio']:.3f} < {args.goodput}")
            if p["summary"]["errors"]:
                problems.append(
                    f"phase {p['name']}: {p['summary']['errors']} failed "
                    "in-flight requests (scale events must be lossless)")
            changes = _direction_changes(
                _phase_events(events, p["t0"], p["t1"]))
            if changes > 1:
                problems.append(f"phase {p['name']}: {changes} scale-"
                                "direction changes (flapping; want <= 1)")
        for e in downs:
            if not e.get("drained"):
                problems.append(
                    f"scale-down of {e.get('url')} was not clean: "
                    f"exit={e.get('exit_code')} "
                    f"inflight={e.get('inflight_at_term')}")
            share = e.get("fleet_affinity_keys") or {}
            if share and e.get("victim_affinity_keys", 0) > min(share.values()):
                problems.append(
                    f"scale-down victim {e.get('url')} was not the "
                    f"idle-most replica (affinity share "
                    f"{e.get('victim_affinity_keys')} vs fleet {share})")
        for who, v in violations.items():
            if v:
                problems.append(f"{who}: {v:.0f} sanitizer violations")
        for url, used in leak.items():
            if used:
                problems.append(f"{url}: {used:.0f} KV blocks still in "
                                "use after quiesce (pool leak)")

        artifact = {
            "metric": "chaos_elasticity",
            "fast": bool(args.fast),
            "seed": args.seed,
            "min_replicas": args.min_replicas,
            "max_replicas": args.max_replicas,
            "final_actual": final_actual,
            "phases": phases,
            "events": events,
            "autoscaler": {k: scaler_debug[k] for k in
                           ("desired", "actual", "converged", "policy",
                            "decisions")},
            "server_router": {
                "backends": router_debug.get("backends"),
                "requests": router_debug.get("requests"),
                "failovers": router_debug.get("failovers"),
                "affinity": router_debug.get("affinity"),
            },
            "survivors": survivor_stats,
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
            _router_log_tail()
            return 1
        _log(f"ok: scaled {args.min_replicas} -> "
             f"{max(p['actual_after'] or 0 for p in phases)} -> "
             f"{final_actual} with goodput "
             f"{min(p['summary']['goodput_ratio'] for p in phases):.3f} "
             f"and {len(downs)} clean drain(s)")
        return 0
    finally:
        if scaler is not None:
            scaler.close()
        executor.close()
        if router_proc is not None and router_proc.poll() is None:
            router_proc.kill()
            try:
                router_proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--fast", action="store_true",
                   help="tier-1/CI shape: floor 1 / ceiling 2, short "
                        "phases")
    p.add_argument("--min-replicas", type=int, default=None,
                   help="replica floor (default: 1)")
    p.add_argument("--max-replicas", type=int, default=None,
                   help="replica ceiling (default: 3, --fast: 2)")
    p.add_argument("--quiet-duration", type=float, default=None,
                   help="quiet phase horizon seconds (default: 8, "
                        "--fast: 4)")
    p.add_argument("--surge-duration", type=float, default=None,
                   help="surge phase horizon seconds (default: 15, "
                        "--fast: 8)")
    p.add_argument("--quiet-tenants",
                   default="interactive:1:interactive",
                   help="replay tenant spec for the quiet phases")
    p.add_argument("--surge-tenants",
                   default="interactive:5:interactive,batch:2:batch",
                   help="replay tenant spec for the surge phase")
    p.add_argument("--target-load", type=float, default=2.0,
                   help="autoscaler work units per replica")
    p.add_argument("--down-cooldown", type=float, default=6.0,
                   help="autoscaler scale-down cooldown seconds")
    p.add_argument("--settle-timeout", type=float, default=90.0,
                   help="max seconds to wait for the post-surge "
                        "scale-down to the floor")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--goodput", type=float, default=0.9,
                   help="per-phase interactive goodput_ratio floor")
    p.add_argument("--out", default="", help="write the JSON artifact here")
    args = p.parse_args(argv)

    args.min_replicas = args.min_replicas or 1
    args.max_replicas = args.max_replicas or (2 if args.fast else 3)
    args.quiet_duration = args.quiet_duration or (4.0 if args.fast else 8.0)
    args.surge_duration = args.surge_duration or (8.0 if args.fast else 15.0)
    if args.min_replicas < 1:
        p.error("--min-replicas must be >= 1")
    if args.max_replicas <= args.min_replicas:
        p.error("--max-replicas must exceed --min-replicas (nothing to "
                "scale otherwise)")
    return run_drill(args)


if __name__ == "__main__":
    sys.exit(main())
