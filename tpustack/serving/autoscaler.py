"""Elastic capacity controller: metric-driven autoscaling for the LLM fleet.

A dependency-free control loop (stdlib urllib + threading, same zero-dep
discipline as the router) that scrapes the L7 router's fleet view
(``/debug/router``) and every replica's health/metrics surfaces, computes
a desired replica count through a DAMPED policy, and executes it through
a pluggable :class:`ScaleExecutor`:

- :class:`LocalSubprocessExecutor` spawns/retires real ``llm_server``
  subprocesses and rewrites the router's ``@file`` registry — the
  CPU-testable executor ``tools/chaos_elasticity.py`` drills.
- :class:`KubernetesExecutor` patches the managed Deployment's ``scale``
  subresource through the API server with the in-cluster service-account
  token (shipped as ``cluster-config/apps/llm/autoscaler-deployment.yaml``
  with an RBAC Role granting ONLY ``deployments/scale`` patch).

The policy is a target-utilization controller with the damping a serving
fleet needs (kubernetes' HPA stabilization window, distilled):

- **load** = Σ over routable replicas of (in-flight + queued) requests.
- scale UP when load exceeds ``actual * target * (1 + hysteresis)``, or
  immediately on shed pressure (replicas refused work this tick) or KV
  pressure (pool free-block ratio under the floor) — capacity problems
  the load sum underestimates because refused work never queues.
- scale DOWN only when load falls under ``(actual-1) * target *
  (1 - hysteresis)`` — the dead band between the walls prevents limit
  cycling — AND the down desire held for ``DOWN_STABLE_TICKS``
  consecutive ticks AND the down cooldown elapsed since ANY scale event
  AND every registered backend is healthy (the hard floor: never give
  back capacity while the router is already steering around a corpse).
- up cooldown is short, down cooldown long: adding capacity under
  pressure must be fast, giving back a warm KV cache must never be hasty.

Scale-DOWN is choreographed, not abrupt.  The victim is the replica with
the smallest affinity ledger share (fewest warm prefixes — the cheapest
cache to lose, read from ``/debug/router``).  The executor then:

1. ``POST /admin/drain`` (authenticated) — ``/readyz`` flips 503 with
   ``X-Shed-Reason: draining`` and the router ejects the victim
   authoritatively within one health tick; no new work arrives,
2. removes it from the registry,
3. polls the victim's ``/healthz`` until in-flight + queued work is zero,
4. and only then sends SIGTERM, which runs the one-shot drain state
   machine and exits 0.

A scale event therefore never loses a request or a warm KV cache it
didn't have to — ``tools/chaos_elasticity.py`` asserts exactly that.

Bisection contract: ``TPUSTACK_AUTOSCALER_ROUTER_URL`` unset/empty
constructs nothing (``maybe_from_env`` returns None).
"""

from __future__ import annotations

import json
import math
import os
import re
import shlex
import signal
import subprocess
import threading
import time
import urllib.request
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

from aiohttp import web

from tpustack import sanitize
from tpustack.obs import catalog as obs_catalog
from tpustack.obs import http as obs_http
from tpustack.utils import get_logger, knobs

log = get_logger("serving.autoscaler")

#: raw per-tick policy desires (the ``policy_decision`` gauge encoding)
UP, HOLD, DOWN = "up", "hold", "down"
_DECISION_GAUGE = {UP: 1, HOLD: 0, DOWN: -1}

#: shed reasons that mean "capacity", not "policy": quota sheds are a
#: tenant exceeding its contract and must never trigger a scale-up, and
#: draining sheds are our own choreography talking back to us
PRESSURE_SHED_REASONS = ("backpressure", "out_of_kv_blocks")

_METRIC_LINE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>[^\s]+)$")


def _fetch_json(url: str, timeout: float = 5.0,
                token: str = "", method: str = "GET",
                body: Optional[dict] = None) -> dict:
    data = None
    headers = {}
    if body is not None:
        data = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    if token:
        headers["X-Admin-Token"] = token
    req = urllib.request.Request(url, data=data, headers=headers,
                                 method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _scrape_metrics(url: str, names: Sequence[str],
                    timeout: float = 5.0) -> List[Dict]:
    """Tolerant text-format scrape: ``[{name, labels, value}, ...]`` for
    the requested families only (labels left as the raw inner string —
    callers substring-match, which is all the policy needs)."""
    req = urllib.request.Request(url.rstrip("/") + "/metrics")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        text = resp.read().decode()
    out = []
    wanted = tuple(names)
    for line in text.splitlines():
        if not line.startswith(wanted):
            continue
        m = _METRIC_LINE.match(line.strip())
        if not m:
            continue
        try:
            value = float(m.group("value"))
        except ValueError:
            continue
        out.append({"name": m.group("name"),
                    "labels": m.group("labels") or "",
                    "value": value})
    return out


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# --------------------------------------------------------------- executors
class ScaleExecutor:
    """What the policy actuates through.  ``actual()`` is the ground
    truth replica count; ``scale_to`` moves it and returns one event dict
    per replica touched (``direction``, ``url``/detail, and for downs the
    drain choreography report)."""

    def actual(self) -> Optional[int]:
        raise NotImplementedError

    def scale_to(self, desired: int,
                 victims: Sequence[str]) -> List[Dict]:
        raise NotImplementedError

    def close(self) -> None:  # pragma: no cover - default no-op
        pass


class LocalSubprocessExecutor(ScaleExecutor):
    """CPU-testable executor: real ``llm_server`` subprocesses + the
    router's ``@file`` registry as the membership mechanism.

    Scale-up spawns a replica on a free port, waits for ``/readyz`` 200
    (so the router never admits a still-compiling backend), then appends
    it to the registry file.  Scale-down runs the drain choreography
    documented in the module docstring and reports it per victim."""

    def __init__(self, registry_file: str,
                 spawn: Callable[[int], List[str]],
                 env: Optional[Dict[str, str]] = None,
                 cwd: Optional[str] = None,
                 admin_token: str = "",
                 log_dir: Optional[str] = None,
                 ready_timeout_s: float = 240.0,
                 drain_timeout_s: float = 120.0):
        self.registry_file = registry_file
        self.spawn = spawn  # port -> argv
        self.spawn_env = env
        self.cwd = cwd
        self.admin_token = admin_token
        self.log_dir = log_dir
        self.ready_timeout_s = ready_timeout_s
        self.drain_timeout_s = drain_timeout_s
        self._lock = threading.Lock()
        # url -> Popen; written by scale_to (control thread), read by
        # debug/teardown paths
        self._procs: Dict[str, subprocess.Popen] = {}  # guarded-by: _lock
        # registry mtime must CHANGE on every rewrite or the router's
        # equal-mtime fast path misses same-second updates; a monotonic
        # bump counter guarantees distinct stamps
        self._mtime_seq = 0
        sanitize.install_guards(self)

    # ------------------------------------------------------------ registry
    def urls(self) -> List[str]:
        with self._lock:
            return sorted(self._procs)

    def _write_registry(self) -> None:
        urls = self.urls()
        with open(self.registry_file, "w") as f:
            f.write("\n".join(urls) + ("\n" if urls else ""))
        self._mtime_seq += 1
        stamp = time.time() + self._mtime_seq * 0.001
        os.utime(self.registry_file, (stamp, stamp))

    # ------------------------------------------------------------ contract
    def actual(self) -> Optional[int]:
        with self._lock:
            return len(self._procs)

    def scale_to(self, desired: int,
                 victims: Sequence[str]) -> List[Dict]:
        events: List[Dict] = []
        current = self.actual() or 0
        for _ in range(max(0, desired - current)):
            events.append(self._spawn_one())
        if desired < current:
            for url in list(victims)[: current - desired]:
                events.append(self._retire(url))
        return events

    # ------------------------------------------------------------ scale up
    def _spawn_one(self) -> Dict:
        port = _free_port()
        url = f"http://127.0.0.1:{port}"
        argv = self.spawn(port)
        stdout = None
        if self.log_dir:
            stdout = open(os.path.join(self.log_dir,
                                       f"replica-{port}.log"), "wb")
        t0 = time.monotonic()
        # Several replicas on one host, none pinned to a chip: a TPU
        # belongs to the first process that initialises a backend on it, so
        # this executor is for CPU replicas (its callers' env sets
        # JAX_PLATFORMS=cpu).  One-chip replicas on a multi-chip host need a
        # per-replica device assignment — out of scope until ROADMAP D7/R10.
        proc = subprocess.Popen(argv, env=self.spawn_env, cwd=self.cwd,
                                stdout=stdout,
                                stderr=subprocess.STDOUT if stdout else None)
        log.info("scale-up: spawned %s (pid %d), waiting for ready",
                 url, proc.pid)
        ready = self._wait_ready(url, proc)
        with self._lock:
            self._procs[url] = proc
        # registered only once ready: the router never sees a backend that
        # would eat its retry budget with connect errors while compiling
        self._write_registry()
        return {"direction": "up", "url": url, "pid": proc.pid,
                "ready": ready,
                "boot_s": round(time.monotonic() - t0, 3)}

    def _wait_ready(self, url: str, proc: subprocess.Popen) -> bool:
        deadline = time.monotonic() + self.ready_timeout_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                log.error("scale-up: replica %s died during boot (exit %s)",
                          url, proc.returncode)
                return False
            try:
                _fetch_json(url + "/readyz", timeout=2)
                return True
            except Exception as exc:
                log.debug("scale-up: %s not ready yet: %s", url, exc)
                time.sleep(0.2)
        log.error("scale-up: replica %s not ready in %.0fs",
                  url, self.ready_timeout_s)
        return False

    # ---------------------------------------------------------- scale down
    def _retire(self, url: str) -> Dict:
        """The zero-loss drain choreography (module docstring, steps 1-4)."""
        t0 = time.monotonic()
        event: Dict = {"direction": "down", "url": url, "drained": False,
                       "exit_code": None, "inflight_at_term": None}
        try:
            _fetch_json(url + "/admin/drain", timeout=5,
                        token=self.admin_token, method="POST", body={})
        except Exception as exc:
            # keep going: registry removal still stops new routing, and
            # SIGTERM still drains — we just lose the authoritative eject
            log.warning("scale-down: admin drain of %s failed: %s", url, exc)
            event["admin_drain_error"] = str(exc)
        with self._lock:
            proc = self._procs.pop(url, None)
        self._write_registry()
        inflight: Optional[int] = None
        deadline = t0 + self.drain_timeout_s
        while time.monotonic() < deadline:
            try:
                h = _fetch_json(url + "/healthz", timeout=2)
                inflight = int(h.get("inflight", 0)) + \
                    int(h.get("queue_depth", 0))
            except Exception as exc:
                # replica gone already — nothing left to wait for
                log.debug("scale-down: %s stopped answering mid-drain "
                          "(%s); treating as drained", url, exc)
                break
            if inflight == 0:
                break
            time.sleep(0.1)
        event["inflight_at_term"] = inflight
        if proc is not None:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
            try:
                event["exit_code"] = proc.wait(timeout=self.drain_timeout_s)
            except subprocess.TimeoutExpired:
                log.error("scale-down: %s ignored SIGTERM; killing", url)
                proc.kill()
                event["exit_code"] = proc.wait(timeout=10)
        event["drain_wait_s"] = round(time.monotonic() - t0, 3)
        event["drained"] = (event["exit_code"] == 0
                            and (inflight in (0, None)))
        log.info("scale-down: retired %s (exit=%s, wait=%.2fs)",
                 url, event["exit_code"], event["drain_wait_s"])
        return event

    def close(self) -> None:
        with self._lock:
            procs = dict(self._procs)
            self._procs.clear()
        for url, proc in procs.items():
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for url, proc in procs.items():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()


class KubernetesExecutor(ScaleExecutor):
    """Patch the managed Deployment's ``scale`` subresource in-cluster.

    Victims are accepted but not chosen here: kubernetes picks the pod to
    delete, and losslessness comes from the replicas' own machinery (the
    preStop sleep + SIGTERM drain state machine, and the router ejecting
    on the authoritative unready probe) rather than from this process.
    The RBAC Role in ``autoscaler-deployment.yaml`` grants exactly this
    one verb on exactly this one subresource."""

    SA_DIR = "/var/run/secrets/kubernetes.io/serviceaccount"

    def __init__(self, namespace: str, deployment: str,
                 api_base: Optional[str] = None,
                 token: Optional[str] = None,
                 transport: Optional[Callable] = None):
        self.namespace = namespace
        self.deployment = deployment
        if api_base is None:
            host = os.environ.get("KUBERNETES_SERVICE_HOST", "")
            port = os.environ.get("KUBERNETES_SERVICE_PORT", "443")
            api_base = f"https://{host}:{port}" if host else ""
        self.api_base = api_base
        if token is None:
            try:
                with open(os.path.join(self.SA_DIR, "token")) as f:
                    token = f.read().strip()
            except OSError:
                token = ""
        self.token = token
        # injectable for tests; the default drives urllib with the
        # service-account CA bundle
        self._transport = transport or self._default_transport

    @property
    def _scale_url(self) -> str:
        return (f"{self.api_base}/apis/apps/v1/namespaces/"
                f"{self.namespace}/deployments/{self.deployment}/scale")

    def _default_transport(self, method: str, url: str,
                           body: Optional[bytes],
                           headers: Dict[str, str]) -> dict:
        import ssl

        cafile = os.path.join(self.SA_DIR, "ca.crt")
        ctx = ssl.create_default_context(
            cafile=cafile if os.path.exists(cafile) else None)
        req = urllib.request.Request(url, data=body, headers=headers,
                                     method=method)
        with urllib.request.urlopen(req, timeout=10, context=ctx) as resp:
            return json.loads(resp.read().decode())

    def _call(self, method: str, body: Optional[dict] = None) -> dict:
        headers = {"Authorization": f"Bearer {self.token}",
                   "Accept": "application/json"}
        data = None
        if body is not None:
            data = json.dumps(body).encode()
            headers["Content-Type"] = "application/merge-patch+json"
        return self._transport(method, self._scale_url, data, headers)

    def actual(self) -> Optional[int]:
        try:
            scale = self._call("GET")
            return int(scale.get("spec", {}).get("replicas", 0))
        except Exception as exc:
            log.warning("scale subresource GET failed: %s", exc)
            return None

    def scale_to(self, desired: int,
                 victims: Sequence[str]) -> List[Dict]:
        current = self.actual()
        try:
            self._call("PATCH", {"spec": {"replicas": desired}})
        except Exception as exc:
            log.error("scale subresource PATCH failed: %s", exc)
            return [{"direction": "error", "error": str(exc)}]
        direction = UP if current is None or desired > current else DOWN
        return [{"direction": direction, "deployment": self.deployment,
                 "namespace": self.namespace, "replicas": desired,
                 "was": current}]


# -------------------------------------------------------------- controller
class Autoscaler:
    """Scrape → decide → execute, on a background thread.

    ``tick()`` is one full control iteration and is directly callable
    (tests drive it synchronously); ``start()`` runs it every
    ``TPUSTACK_AUTOSCALER_INTERVAL_S`` seconds until ``close()``."""

    def __init__(self, router_url: str, executor: ScaleExecutor,
                 registry=None, env=None):
        self.router_url = router_url.rstrip("/")
        self.executor = executor
        self.min_replicas = max(1, knobs.get_int(
            "TPUSTACK_AUTOSCALER_MIN", env=env))
        self.max_replicas = max(self.min_replicas, knobs.get_int(
            "TPUSTACK_AUTOSCALER_MAX", env=env))
        self.target_load = max(0.1, knobs.get_float(
            "TPUSTACK_AUTOSCALER_TARGET_LOAD", env=env))
        self.hysteresis = max(0.0, knobs.get_float(
            "TPUSTACK_AUTOSCALER_HYSTERESIS", env=env))
        self.interval_s = max(0.05, knobs.get_float(
            "TPUSTACK_AUTOSCALER_INTERVAL_S", env=env))
        self.up_cooldown_s = max(0.0, knobs.get_float(
            "TPUSTACK_AUTOSCALER_UP_COOLDOWN_S", env=env))
        self.down_cooldown_s = max(0.0, knobs.get_float(
            "TPUSTACK_AUTOSCALER_DOWN_COOLDOWN_S", env=env))
        self.down_stable_ticks = max(1, knobs.get_int(
            "TPUSTACK_AUTOSCALER_DOWN_STABLE_TICKS", env=env))
        self.kv_free_min = max(0.0, knobs.get_float(
            "TPUSTACK_AUTOSCALER_KV_FREE_MIN", env=env))
        self._registry = registry
        self.metrics = obs_catalog.build(registry)
        self.resilience = None  # the debug app has no admission to manage
        self._lock = threading.Lock()
        #: executed scale events, annotated with victim metadata —
        #: /debug/autoscaler's audit trail and the chaos drill's evidence
        self._events: List[Dict] = []  # guarded-by: _lock
        #: recent per-tick decision records (held ones included)
        self._decisions: deque = deque(maxlen=128)  # guarded-by: _lock
        self._last_signals: Optional[Dict] = None  # guarded-by: _lock (writes)
        self._scaling = False  # guarded-by: _lock (writes)
        # control-thread-only damping state (benign racy reads in debug)
        self._desired = self.min_replicas
        self._down_streak = 0
        self._last_event_at: Optional[float] = None
        self._last_up_at = -math.inf
        self._last_down_at = -math.inf
        self._prev_shed: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        sanitize.install_guards(self)
        log.info("autoscaler up: router=%s min=%d max=%d target=%.1f "
                 "hysteresis=%.2f", self.router_url, self.min_replicas,
                 self.max_replicas, self.target_load, self.hysteresis)

    # ------------------------------------------------------------- scraping
    def observe(self) -> Optional[Dict]:
        """One fleet snapshot, or None when the router is unreachable
        (the loop HOLDS on blindness — scaling on stale data is worse
        than scaling late)."""
        try:
            dbg = _fetch_json(self.router_url + "/debug/router", timeout=5)
        except Exception as exc:
            log.warning("router scrape failed: %s", exc)
            return None
        fleet = dbg.get("backends") or {}
        backends: Dict[str, Dict] = {}
        load = 0
        shed_total = 0.0
        kv_free_ratio: Optional[float] = None
        unhealthy = 0
        for url, st in fleet.items():
            b: Dict = {"state": st.get("state"),
                       "affinity_keys": int(st.get("affinity_keys") or 0),
                       "inflight": 0, "queue_depth": 0}
            if st.get("state") != "healthy":
                unhealthy += 1
            try:
                h = _fetch_json(url + "/healthz", timeout=2)
                b["inflight"] = int(h.get("inflight", 0))
                b["queue_depth"] = int(h.get("queue_depth", 0))
            except Exception as exc:
                log.debug("observe: %s /healthz unreachable: %s", url, exc)
                b["unreachable"] = True
                unhealthy += 0 if st.get("state") != "healthy" else 1
            load += b["inflight"] + b["queue_depth"]
            try:
                samples = _scrape_metrics(url, (
                    "tpustack_requests_shed_total",
                    "tpustack_llm_kv_free_blocks",
                    "tpustack_llm_kv_used_blocks"), timeout=2)
            except Exception as exc:
                log.debug("observe: %s /metrics unreachable: %s", url, exc)
                samples = []
            free = used = None
            for s in samples:
                if s["name"] == "tpustack_requests_shed_total":
                    if any(f'reason="{r}"' in s["labels"]
                           for r in PRESSURE_SHED_REASONS):
                        shed_total += s["value"]
                elif s["name"] == "tpustack_llm_kv_free_blocks":
                    free = s["value"]
                elif s["name"] == "tpustack_llm_kv_used_blocks":
                    used = s["value"]
            if free is not None and used is not None and free + used > 0:
                ratio = free / (free + used)
                b["kv_free_ratio"] = round(ratio, 4)
                kv_free_ratio = (ratio if kv_free_ratio is None
                                 else min(kv_free_ratio, ratio))
            backends[url] = b
        signals = {
            "backends": backends,
            "registered": len(fleet),
            "healthy": int(dbg.get("healthy") or 0),
            "load": load,
            "shed_total": shed_total,
            "kv_free_ratio_min": kv_free_ratio,
            "unhealthy_any": unhealthy > 0,
        }
        with self._lock:
            self._last_signals = signals
        return signals

    # --------------------------------------------------------------- policy
    def decide(self, signals: Dict, actual: int, now: float) -> Dict:
        """The damped policy.  Mutates only the damping state
        (``_down_streak``, ``_prev_shed``); execution happens in
        ``tick``.  Returns the full decision record."""
        load = signals["load"]
        shed_total = signals["shed_total"]
        shed_delta = 0.0
        if self._prev_shed is not None:
            # replicas come and go, so the fleet-sum can step backwards;
            # a negative delta is membership churn, not negative pressure
            shed_delta = max(0.0, shed_total - self._prev_shed)
        self._prev_shed = shed_total
        kv_free = signals["kv_free_ratio_min"]

        up_wall = actual * self.target_load * (1.0 + self.hysteresis)
        down_wall = ((actual - 1) * self.target_load
                     * (1.0 - self.hysteresis))

        raw, reason, want = HOLD, "steady", actual
        if shed_delta > 0:
            raw, reason = UP, "shed_pressure"
            want = actual + 1
        elif kv_free is not None and kv_free < self.kv_free_min:
            raw, reason = UP, "kv_pressure"
            want = actual + 1
        elif load > up_wall:
            raw, reason = UP, "load"
            # jump straight to what the load needs — a surge should not
            # climb one replica per cooldown window
            want = max(actual + 1,
                       math.ceil(load / self.target_load))
        elif actual > self.min_replicas and load < down_wall:
            raw, reason = DOWN, "idle"
            want = actual - 1  # one step per event: each down drains

        # ---- damping ----
        direction, desired = HOLD, actual
        if raw == DOWN:
            self._down_streak += 1
        else:
            self._down_streak = 0
        if raw == UP:
            desired = min(want, self.max_replicas)
            if desired <= actual:
                reason, desired = "bounds", actual
            elif now - self._last_up_at < self.up_cooldown_s:
                reason, desired = "up_cooldown", actual
            else:
                direction = UP
        elif raw == DOWN:
            if signals["unhealthy_any"]:
                # the hard floor: a fleet already steering around a bad
                # backend keeps every healthy replica it has
                reason, desired = "unhealthy_floor", actual
            elif self._down_streak < self.down_stable_ticks:
                reason, desired = "down_stabilizing", actual
            elif (now - max(self._last_up_at, self._last_down_at)
                    < self.down_cooldown_s):
                reason, desired = "down_cooldown", actual
            else:
                direction, desired = DOWN, max(want, self.min_replicas)
                if desired >= actual:
                    direction, desired = HOLD, actual
        return {"raw": raw, "direction": direction, "reason": reason,
                "desired": desired, "actual": actual, "load": load,
                "shed_delta": shed_delta, "kv_free_ratio_min": kv_free,
                "up_wall": round(up_wall, 2),
                "down_wall": round(down_wall, 2),
                "down_streak": self._down_streak}

    def pick_victims(self, signals: Dict, count: int) -> List[str]:
        """Smallest affinity ledger share first (fewest warm prefixes =
        cheapest cache to lose); ties broken by current load, then URL
        for determinism."""
        ranked = sorted(
            signals["backends"].items(),
            key=lambda kv: (kv[1].get("affinity_keys", 0),
                            kv[1].get("inflight", 0)
                            + kv[1].get("queue_depth", 0),
                            kv[0]))
        return [url for url, _ in ranked[:count]]

    # ------------------------------------------------------------- the loop
    def tick(self) -> Dict:
        now = time.monotonic()
        signals = self.observe()
        actual = self.executor.actual()
        if signals is None or actual is None:
            record = {"raw": HOLD, "direction": HOLD,
                      "reason": "scrape_failed", "desired": self._desired,
                      "actual": actual, "t": time.time()}
            with self._lock:
                self._decisions.append(record)
            return record
        record = self.decide(signals, actual, now)
        record["t"] = time.time()
        self._desired = record["desired"]
        self.metrics["tpustack_autoscaler_policy_decision_state"].set(
            _DECISION_GAUGE[record["raw"]])
        self.metrics["tpustack_autoscaler_desired_replicas"].set(
            record["desired"])
        self.metrics["tpustack_autoscaler_actual_replicas"].set(actual)
        with self._lock:
            self._decisions.append(record)
        if record["direction"] == HOLD:
            return record

        victims: List[str] = []
        if record["direction"] == DOWN:
            victims = self.pick_victims(signals,
                                        actual - record["desired"])
        with self._lock:
            self._scaling = True
        try:
            events = self.executor.scale_to(record["desired"], victims)
        finally:
            with self._lock:
                self._scaling = False
        for event in events:
            event = dict(event, reason=record["reason"], t=time.time())
            if event["direction"] == DOWN and event.get("url"):
                b = signals["backends"].get(event["url"], {})
                event["victim_affinity_keys"] = b.get("affinity_keys", 0)
                event["fleet_affinity_keys"] = {
                    u: s.get("affinity_keys", 0)
                    for u, s in signals["backends"].items()}
            self.metrics["tpustack_autoscaler_scale_events_total"].labels(
                direction=event["direction"],
                reason=record["reason"]).inc()
            if event.get("drain_wait_s") is not None:
                self.metrics["tpustack_autoscaler_drain_wait_seconds"] \
                    .observe(event["drain_wait_s"])
            with self._lock:
                self._events.append(event)
        done = time.monotonic()
        self._last_event_at = done
        if record["direction"] == UP:
            self._last_up_at = done
        else:
            self._last_down_at = done
        after = self.executor.actual()
        if after is not None:
            self.metrics["tpustack_autoscaler_actual_replicas"].set(after)
        record["events"] = events
        return record

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:
                log.exception("autoscaler tick failed; holding")

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="tpustack-autoscaler")
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(5.0, self.interval_s * 2))
            self._thread = None

    # ---------------------------------------------------------------- views
    def debug_payload(self) -> Dict:
        actual = self.executor.actual()
        with self._lock:
            events = list(self._events)
            decisions = list(self._decisions)[-16:]
            signals = self._last_signals
            scaling = self._scaling
        desired = self._desired
        last_age = (round(time.monotonic() - self._last_event_at, 3)
                    if self._last_event_at is not None else None)
        return {
            "desired": desired,
            "actual": actual,
            "converged": (actual == desired and not scaling),
            "scaling_in_progress": scaling,
            "last_event_age_s": last_age,
            "policy": {
                "min": self.min_replicas,
                "max": self.max_replicas,
                "target_load": self.target_load,
                "hysteresis": self.hysteresis,
                "interval_s": self.interval_s,
                "up_cooldown_s": self.up_cooldown_s,
                "down_cooldown_s": self.down_cooldown_s,
                "down_stable_ticks": self.down_stable_ticks,
                "kv_free_min": self.kv_free_min,
            },
            "signals": signals,
            "decisions": decisions,
            "events": events,
        }

    async def debug_autoscaler(self, request: web.Request) -> web.Response:
        return web.json_response(self.debug_payload())

    async def health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok"})

    async def healthz(self, request: web.Request) -> web.Response:
        return web.json_response({"ok": True, "desired": self._desired,
                                  "actual": self.executor.actual()})

    async def readyz(self, request: web.Request) -> web.Response:
        # ready as long as the loop thread lives: a blind autoscaler
        # HOLDS, which is safe — restarting it buys nothing
        alive = self._thread is not None and self._thread.is_alive()
        return web.json_response({"ready": alive},
                                 status=200 if alive else 503)

    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/health", self.health)
        app.router.add_get("/healthz", self.healthz)
        app.router.add_get("/readyz", self.readyz)
        app.router.add_get("/metrics",
                           obs_http.make_metrics_handler(self._registry))
        app.router.add_get("/debug/autoscaler", self.debug_autoscaler)
        return app


# ------------------------------------------------------------------ wiring
def executor_from_env(env=None) -> Optional[ScaleExecutor]:
    registry_file = knobs.get_str(
        "TPUSTACK_AUTOSCALER_REGISTRY_FILE", env=env).strip()
    if registry_file:
        template = knobs.get_str(
            "TPUSTACK_AUTOSCALER_SPAWN_CMD", env=env).strip()
        if not template:
            raise ValueError("TPUSTACK_AUTOSCALER_REGISTRY_FILE is set but "
                             "TPUSTACK_AUTOSCALER_SPAWN_CMD is not")

        def spawn(port: int) -> List[str]:
            return [a.replace("{port}", str(port))
                    for a in shlex.split(template)]

        return LocalSubprocessExecutor(
            registry_file, spawn,
            admin_token=knobs.get_str("TPUSTACK_ADMIN_TOKEN", env=env),
            drain_timeout_s=knobs.get_float(
                "TPUSTACK_AUTOSCALER_DRAIN_TIMEOUT_S", env=env))
    deployment = knobs.get_str(
        "TPUSTACK_AUTOSCALER_K8S_DEPLOYMENT", env=env).strip()
    if deployment:
        return KubernetesExecutor(
            knobs.get_str("TPUSTACK_AUTOSCALER_K8S_NAMESPACE", env=env),
            deployment)
    return None


def maybe_from_env(registry=None, env=None) -> Optional[Autoscaler]:
    """The bisection contract: ``TPUSTACK_AUTOSCALER_ROUTER_URL``
    unset/empty constructs NOTHING."""
    router_url = knobs.get_str(
        "TPUSTACK_AUTOSCALER_ROUTER_URL", env=env).strip()
    if not router_url:
        return None
    executor = executor_from_env(env=env)
    if executor is None:
        raise ValueError(
            "autoscaler needs an executor: set "
            "TPUSTACK_AUTOSCALER_REGISTRY_FILE (+_SPAWN_CMD) or "
            "TPUSTACK_AUTOSCALER_K8S_DEPLOYMENT")
    return Autoscaler(router_url, executor, registry=registry, env=env)


def main() -> None:
    scaler = maybe_from_env()
    if scaler is None:
        raise SystemExit("TPUSTACK_AUTOSCALER_ROUTER_URL is not set — "
                         "nothing to scale")
    scaler.start()
    obs_http.maybe_start_metrics_sidecar()
    port = int(os.environ.get("PORT", "8091"))
    web.run_app(scaler.build_app(), port=port, access_log=None)


if __name__ == "__main__":
    main()
