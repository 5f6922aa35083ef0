#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on
the chip.

Drives the stack's main path once, through the entry points a user calls,
at the full width of the model the LLM Deployment serves:

- **Phase A — device.**  ``python -m tpustack.ops.vectoradd`` must end
  ``Test PASSED`` on the ``tpu`` backend.
- **Phase B — the served path.**  ``python -m tpustack.serving.llm_server``
  with the Deployment's model env and nothing else changed from the
  defaults (Qwen2.5-7B shape, int8 weights, int8 KV, ctx 4096, 8 slots;
  random weights from the server's fixed seed — no network).  After
  ``/readyz``: one greedy completion over a repetitive ~512-token prompt,
  the same body again (the paged prefix cache's hit counter must rise),
  eight concurrent completions with prompt lengths spread over 32–2048
  tokens, one streamed chat completion.  Every response is 200 with at
  least one generated token.  Then ``/props``, ``/debug/flight`` and
  ``/metrics`` must agree on what served: platform ``tpu``, paged KV and
  speculation on, every wave record's kernel equal to the kernel ``/props``
  names, HBM in use above the weight bytes on every chip of the mesh.
  Finally SIGTERM: the server drains and exits 0.

This parent imports neither jax nor tpustack — a chip belongs to the one
process that initialised a backend on it — and starts each phase as a child,
one after another, talking HTTP with the standard library.  Children inherit
the environment untouched (``JAX_COMPILATION_CACHE_DIR`` included) plus the
model env above.

stdout carries two lines, and only on success.  The LAST is the result, one
JSON object with exactly these keys: ``{"ok": true, "device": {"platform":
"tpu", "kind": …, "count": …}}`` — the device as JAX reports it through
``/props``.  The line before it is ``{"observations": {…}}``: the kernel that
served, wave count, mesh and HBM bytes, speculation counters, the
compile-cache directory with its entry count before/after, and per-phase wall
seconds (observations, not metrics; also kept in
``chiprun_out/chip_smoke/observations.json``).  Any failed phase: the reason
and the child's log tail on stderr, nothing on stdout, exit 1 — which is also
what happens on a machine where JAX finds no TPU, or in a directory that
holds only this file.

``--cpu-rehearsal`` (with ``JAX_PLATFORMS=cpu`` set explicitly) runs the same
control flow here on the CPU at the tiny preset with the paged kernel in
interpret mode, so chip time is not spent debugging this script; its result
line says platform ``cpu`` (and the observations ``"rehearsal": true``).  ``--server-env K=V``
adds to the server child's env (``LLM_TP=4`` on a four-chip host).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
BUDGET_S = 1150  # the contract's 1200 s, less a margin to clean up in

#: the LLM Deployment's model env (cluster-config/apps/llm/deployment.yaml)
SERVER_ENV = {"LLM_PRESET": "qwen25_7b", "LLM_QUANT": "int8",
              "LLM_KV_QUANT": "int8", "LLM_CTX": "4096",
              "LLM_MAX_BATCH": "8"}
#: --cpu-rehearsal: same flags at the tiny preset (ctx caps at 128 there);
#: the kernel forced on, because `auto` picks the gather path off-TPU
REHEARSAL_ENV = {"LLM_PRESET": "tiny", "LLM_CTX": "128",
                 "TPUSTACK_PAGED_FLASH": "1"}

PHRASE = "the quick brown fox jumps over the lazy dog and "

T0 = time.monotonic()


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def remaining(cap: float) -> float:
    left = BUDGET_S - (time.monotonic() - T0)
    if left <= 0:
        raise SmokeFailure(f"out of time ({BUDGET_S}s budget)")
    return min(cap, left)


def tail(path: str, lines: int = 40, width: int = 300) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - 65536))
            text = f.read().decode("utf-8", "replace")
    except OSError as e:
        return f"<no log: {e}>"
    return "\n".join(ln[:width] for ln in text.splitlines()[-lines:])


# ------------------------------------------------------------------ phase A
def phase_device(expect_platform: str) -> float:
    t0 = time.monotonic()
    log_path = os.path.join(OUT_DIR, "phase_a_vectoradd.log")
    with open(log_path, "w") as out:
        try:
            rc = subprocess.run(
                [sys.executable, "-m", "tpustack.ops.vectoradd"], cwd=ROOT,
                stdout=out, stderr=subprocess.STDOUT,
                timeout=remaining(300)).returncode
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"phase A: vectoradd hung\n{tail(log_path)}")
    text = tail(log_path, lines=200, width=2000)
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if rc != 0 or not lines or lines[-1].strip() != "Test PASSED":
        raise SmokeFailure(f"phase A: vectoradd exit {rc}, no 'Test PASSED'"
                           f"\n{text}")
    if f"backend={expect_platform} " not in text:
        raise SmokeFailure(f"phase A: vectoradd passed, but not on "
                           f"{expect_platform!r}\n{text}")
    return time.monotonic() - t0


# ------------------------------------------------------------------ phase B
class Server:
    """The llm_server child and a stdlib HTTP client for it."""

    def __init__(self, env_extra: dict):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        env = dict(os.environ, **env_extra, PORT=str(self.port))
        env.pop("MODEL_DIR", None)  # random weights: the copy has no network
        self.log_path = os.path.join(OUT_DIR, "phase_b_llm_server.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tpustack.serving.llm_server"], cwd=ROOT,
            env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def http(self, method: str, path: str, body=None, timeout: float = 600,
             probe: bool = False):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=remaining(timeout)) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()
        except (urllib.error.URLError, OSError) as e:
            if probe:  # wait_ready polls a port nothing listens on yet
                return None, b""
            raise SmokeFailure(f"{method} {path}: {e!r} (server "
                               f"{'gone' if self.proc.poll() is not None else 'up'})")

    def get_json(self, path: str):
        status, raw = self.http("GET", path, timeout=60)
        if status != 200:
            raise SmokeFailure(f"GET {path} → {status}: {raw[:300]!r}")
        return json.loads(raw)

    def post_json(self, path: str, body: dict, what: str):
        status, raw = self.http("POST", path, body)
        if status != 200:
            raise SmokeFailure(f"{what}: POST {path} → {status}: "
                               f"{raw[:400]!r}")
        return json.loads(raw)

    def metrics(self) -> str:
        status, raw = self.http("GET", "/metrics", timeout=60)
        if status != 200:
            raise SmokeFailure(f"GET /metrics → {status}")
        return raw.decode()

    def wait_ready(self) -> None:
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited {self.proc.returncode} before /readyz")
            if self.http("GET", "/readyz", timeout=5, probe=True)[0] == 200:
                return
            remaining(1)
            time.sleep(1.0)

    def stop(self) -> None:
        """Whatever happened, leave no process behind."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        self._log.close()


def make_prompt(srv: Server, target_tokens: int, tag: str = "") -> str:
    """A repetitive prompt of about ``target_tokens`` (prompt-lookup needs
    something to draft from), sized with the server's own tokenizer."""
    sample = PHRASE * 8
    per_char = len(srv.post_json("/tokenize", {"content": sample},
                                 "tokenize")["tokens"]) / len(sample)
    chars = max(len(tag) + 8, int((target_tokens - 2) / per_char))
    return (tag + PHRASE * (chars // len(PHRASE) + 1))[:chars]


def completion(srv: Server, prompt: str, n_predict: int, what: str) -> dict:
    out = srv.post_json("/completion", {"prompt": prompt, "temperature": 0,
                                        "n_predict": n_predict}, what)
    if out.get("tokens_predicted", 0) < 1:
        raise SmokeFailure(f"{what}: no token generated: {out}")
    return out


def metric_value(metrics_text: str, name: str, labels: str = "") -> float:
    want = name + labels
    for line in metrics_text.splitlines():
        if line.startswith(want + " "):
            return float(line.split()[-1])
    raise SmokeFailure(f"/metrics has no sample {want}")


def stream_chat(srv: Server, prompt_tokens: int, n_predict: int) -> None:
    before = metric_value(srv.metrics(),
                          "tpustack_llm_generated_tokens_total")
    status, raw = srv.http("POST", "/v1/chat/completions", {
        "messages": [{"role": "user",
                      "content": make_prompt(srv, prompt_tokens)}],
        "max_tokens": n_predict, "temperature": 0, "stream": True})
    events = [ln[6:] for ln in raw.decode("utf-8", "replace").splitlines()
              if ln.startswith("data: ")]
    if status != 200 or not events or events[-1] != "[DONE]":
        raise SmokeFailure(f"streamed chat: status {status}, "
                           f"{len(events)} events, tail {events[-2:]}")
    chunks = [json.loads(e) for e in events[:-1]]
    finishes = [c["choices"][0]["finish_reason"] for c in chunks
                if c["choices"][0]["finish_reason"]]
    if finishes not in (["length"], ["stop"]):
        raise SmokeFailure(f"streamed chat: finish reasons {finishes}")
    made = metric_value(srv.metrics(),
                        "tpustack_llm_generated_tokens_total") - before
    if made < 1:
        raise SmokeFailure("streamed chat: no token generated")


def check_served_state(srv: Server, expect_platform: str) -> dict:
    """/props, /debug/flight and /metrics must agree on what served."""
    props = srv.get_json("/props")
    backend = props["backend"]
    if backend["platform"] != expect_platform:
        raise SmokeFailure(f"/props.backend is {backend}, "
                           f"want platform {expect_platform!r}")
    if not props["paged_kv"]["enabled"]:
        raise SmokeFailure(f"paged KV is off: {props['paged_kv']}")
    if not props["speculative"]["enabled"]:
        raise SmokeFailure(f"speculation is off: {props['speculative']}")
    kernel = props["paged_kv"]["kernel"]
    waves = [r for r in srv.get_json("/debug/flight")["records"]
             if r.get("kind") == "wave"]
    if not waves:
        raise SmokeFailure("/debug/flight holds no wave record")
    odd = sorted({str(r.get("kernel")) for r in waves} - {kernel})
    if odd:
        raise SmokeFailure(f"/props names kernel {kernel!r} but wave "
                           f"records carry {odd}")
    mesh = props["mesh"]
    if mesh["tp"] > 1 and not mesh["kv_head_sharded"]:
        raise SmokeFailure(f"tp={mesh['tp']} but the KV is not head-sharded:"
                           f" {mesh}")
    hbm = None
    if expect_platform == "tpu":
        metrics = srv.metrics()
        hbm = [metric_value(metrics, "tpustack_device_hbm_used_bytes",
                            f'{{device="tpu:{i}"}}')
               for i in range(max(1, mesh["tp"]))]
        if min(hbm) <= mesh["weights_per_chip_bytes"]:
            raise SmokeFailure(
                f"HBM in use per chip {hbm} is not above the per-chip "
                f"weight bytes {mesh['weights_per_chip_bytes']}")
    spec = props["speculative"]
    return {
        "device": backend,
        "kernel": kernel,
        "waves": len(waves),
        "mesh": {k: mesh[k] for k in ("tp", "kv_head_sharded",
                                      "weights_per_chip_bytes",
                                      "kv_per_chip_bytes")},
        "hbm_used_bytes": hbm,
        "prefix_cache_hits": props["prefix_cache"]["hits"],
        "speculative": {
            "drafted_tokens": spec["drafted_tokens"],
            "accepted_tokens": spec["accepted_tokens"],
            # reported, not asserted: whether the drafter gets a turn
            # depends on host timing today (ROADMAP S3)
            "verify_exercised": spec["drafted_tokens"] > 0,
        },
    }


def phase_served_path(env_extra: dict, expect_platform: str,
                      rehearsal: bool) -> dict:
    # prompt/answer sizes: the Deployment's ctx 4096, or the tiny ctx 128
    first_len, n_first, n_rest = (48, 16, 8) if rehearsal else (512, 64, 32)
    spread = ([8, 12, 16, 24, 32, 40, 48, 64] if rehearsal else
              [32, 64, 128, 256, 512, 1024, 1536, 2048])
    secs = {}
    t0 = time.monotonic()
    srv = Server(env_extra)
    try:
        srv.wait_ready()
        secs["boot_to_ready"] = time.monotonic() - t0
        log(f"server ready after {secs['boot_to_ready']:.1f}s")

        body = make_prompt(srv, first_len)
        t = time.monotonic()
        first = completion(srv, body, n_first, "first request")
        secs["first_request"] = time.monotonic() - t
        log(f"first request: {first['tokens_evaluated']} prompt tok, "
            f"{first['tokens_predicted']} generated, "
            f"{secs['first_request']:.1f}s")
        with open(os.path.join(OUT_DIR, "first_request.json"), "w") as f:
            json.dump(first, f)

        hits0 = srv.get_json("/props")["prefix_cache"]["hits"]
        t = time.monotonic()
        completion(srv, body, n_first, "repeated request")
        secs["repeated_request"] = time.monotonic() - t
        hits1 = srv.get_json("/props")["prefix_cache"]["hits"]
        if hits1 <= hits0:
            raise SmokeFailure(f"repeated request: prefix-cache hits stayed "
                               f"at {hits1}")
        log(f"repeated request: {secs['repeated_request']:.1f}s, "
            f"prefix hits {hits0} → {hits1}")

        prompts = [make_prompt(srv, n, f"request {i}: ")
                   for i, n in enumerate(spread)]
        results: list = [None] * len(prompts)

        def one(i):
            try:
                results[i] = completion(srv, prompts[i], n_rest,
                                        f"concurrent request {i}")
            except Exception as e:  # carried to the main thread below
                results[i] = e

        t = time.monotonic()
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(remaining(900))
        secs["concurrent_8"] = time.monotonic() - t
        for i, r in enumerate(results):
            if not isinstance(r, dict):
                raise SmokeFailure(f"concurrent request {i}: {r!r}")
        log("8 concurrent: prompt tokens "
            f"{[r['tokens_evaluated'] for r in results]}, "
            f"{secs['concurrent_8']:.1f}s")

        t = time.monotonic()
        stream_chat(srv, spread[2], n_rest)
        secs["streamed_chat"] = time.monotonic() - t

        state = check_served_state(srv, expect_platform)

        t = time.monotonic()
        srv.proc.send_signal(signal.SIGTERM)
        try:
            rc = srv.proc.wait(timeout=remaining(120))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server did not exit within 120s of SIGTERM")
        if rc != 0:
            raise SmokeFailure(f"server exited {rc} after SIGTERM, want 0")
        secs["drain_exit"] = time.monotonic() - t
    except SmokeFailure as e:
        raise SmokeFailure(f"phase B: {e}\n--- server log tail ---\n"
                           f"{tail(srv.log_path)}")
    finally:
        srv.stop()
    return dict(state, seconds=secs)


# --------------------------------------------------------------------- main
def cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".cache", "xla"))


def cache_entries() -> int:
    try:
        return sum(1 for e in os.scandir(cache_dir()) if e.is_file())
    except OSError:
        return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="run the control flow on the CPU at the tiny preset "
                        "(needs JAX_PLATFORMS=cpu set explicitly)")
    p.add_argument("--server-env", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="extra env for the server child, e.g. LLM_TP=4")
    args = p.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    if rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        p.error("--cpu-rehearsal needs JAX_PLATFORMS=cpu in the environment")
    expect = "cpu" if rehearsal else "tpu"
    env_extra = dict(SERVER_ENV, **(REHEARSAL_ENV if rehearsal else {}))
    env_extra.update(kv.split("=", 1) for kv in args.server_env)

    os.makedirs(OUT_DIR, exist_ok=True)
    entries_before = cache_entries()
    try:
        secs_a = phase_device(expect)
        log(f"phase A passed on {expect} in {secs_a:.1f}s")
        served = phase_served_path(env_extra, expect, rehearsal)
    except SmokeFailure as e:
        log(f"FAILED — {e}")
        return 1
    seconds = {"phase_a": secs_a, **served.pop("seconds"),
               "total": time.monotonic() - T0}
    device = served.pop("device")
    observations = {
        "rehearsal": rehearsal,
        **served,
        "cache": {"dir": cache_dir(), "entries_before": entries_before,
                  "entries_after": cache_entries()},
        "seconds": {k: round(v, 1) for k, v in seconds.items()},
    }
    if not observations["speculative"]["verify_exercised"]:
        observations["speculative"]["note"] = (
            "no draft was proposed, so the verify program (the S=5 kernel "
            "shape) was not exercised here; tests/test_tpu_hw.py covers it")
    with open(os.path.join(OUT_DIR, "observations.json"), "w") as f:
        json.dump(observations, f)
    print(json.dumps({"observations": observations}), flush=True)
    # the result line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
