"""Runner: the LLM server, in this process, driven over loopback HTTP.

This process holds the chip: it builds the configuration's ``Generator`` from
the benchmark's own seeded weights, hosts ``LLMServer.build_app()`` on
``127.0.0.1:0`` in a thread (the pattern of ``tools/replay.py::_SelfHosted``),
warms the cell's shapes through that same entry, and is the only process that
can trace the device.  The timed traffic comes from a child process
(``benchmark/loadgen.py``, standard library only), so the clients' threads do
not share this interpreter's lock with the engine.

From the program it takes: ``LlamaConfig``, ``Generator``, ``LLMServer``
(constructor arguments only), and the server's HTTP endpoints.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional

from benchmark import idtok, loadgen

#: a compile event whose function name has this in it is an admission
#: program: how the warm-up knows that a burst was admitted as one group
ADMIT_HINT = "admit"


def log(msg: str) -> None:
    print(f"[llm_http {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ------------------------------------------------------------ the program
def program_params(weights) -> Dict:
    """The benchmark's weights in the tree ``LlamaModel`` reads."""

    def dense(w, bias=None):
        out = ({"kernel": w["q"], "scale": w["s"]} if isinstance(w, dict)
               else {"kernel": w})
        if bias is not None:
            out["bias"] = bias
        return out

    emb = weights.embed()
    tree = {"embed_tokens": ({"embedding": emb["q"], "scale": emb["s"]}
                            if isinstance(emb, dict) else {"embedding": emb}),
            "norm": {"scale": weights.final_norm()}}
    for i in range(weights.n_layers):
        w = weights.layer(i)
        tree[f"layers_{i}"] = {
            "input_layernorm": {"scale": w["ln1"]},
            "post_attention_layernorm": {"scale": w["ln2"]},
            "self_attn": {"q_proj": dense(w["wq"], w["bq"]),
                          "k_proj": dense(w["wk"], w["bk"]),
                          "v_proj": dense(w["wv"], w["bv"]),
                          "o_proj": dense(w["wo"])},
            "mlp": {"gate_proj": dense(w["w_gate"]),
                    "up_proj": dense(w["w_up"]),
                    "down_proj": dense(w["w_down"])}}
    head = weights.head()
    if head is not None:
        tree["lm_head"] = dense(head)
    return tree


def build_server(cfg: Dict, weights):
    import jax
    import jax.numpy as jnp

    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_generate import Generator
    from tpustack.serving.llm_server import LLMServer

    for k, v in (cfg.get("env") or {}).items():
        os.environ[k] = str(v)
    # the serving stack logs to stdout; this process's stdout ends with the
    # result line, so its chatter goes to stderr
    import logging

    for h in logging.getLogger("tpustack").handlers:
        if getattr(h, "stream", None) is sys.stdout:
            h.setStream(sys.stderr)
    lc = LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], max_seq=cfg["ctx"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]), qkv_bias=True,
        tie_embeddings=bool(cfg.get("tie_word_embeddings")),
        quant="int8" if cfg.get("weights") == "int8" else None,
        kv_quant="int8" if cfg.get("kv") == "int8" else None)
    params = program_params(weights)
    jax.block_until_ready(params)
    gen = Generator(lc, params=params, dtype=jnp.bfloat16)
    del params
    return LLMServer(generator=gen,
                     tokenizer=idtok.IdTokenizer(cfg["vocab_size"]),
                     model_name=cfg["name"], max_batch=int(cfg["slots"]))


class Hosted:
    """``server.build_app()`` on a loopback port, in a thread."""

    def __init__(self, server):
        from aiohttp import web

        self.server = server
        self._loop = asyncio.new_event_loop()
        started = threading.Event()
        self.port = None

        def run():
            asyncio.set_event_loop(self._loop)

            async def start():
                runner = web.AppRunner(server.build_app(), access_log=None)
                await runner.setup()
                site = web.TCPSite(runner, "127.0.0.1", 0)
                await site.start()
                self.port = runner.addresses[0][1]
                started.set()
                return runner

            self._runner = self._loop.run_until_complete(start())
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="bench-llm-host")
        self._thread.start()
        if not started.wait(timeout=120):
            raise RuntimeError("the server did not start")
        self.url = f"http://127.0.0.1:{self.port}"

    def get(self, path: str, timeout: float = 30.0) -> bytes:
        with urllib.request.urlopen(self.url + path, timeout=timeout) as r:
            return r.read()

    def close(self) -> None:
        fut = asyncio.run_coroutine_threadsafe(self._runner.cleanup(),
                                               self._loop)
        try:
            fut.result(timeout=60)
        finally:
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=30)


# --------------------------------------------------------------- warm-up
def warm_up(host: Hosted, traffic: Dict, seed: int, vocab: int,
            events: List[Dict], warm: Dict) -> Dict:
    """Every admission shape the window can meet, through the served entry:
    groups of 1, 2, ... ``clients`` rows of the cell's one prompt bucket,
    then one greedy repetitive request for the verify program.

    A group of k rows is admitted together when k requests wait at one chunk
    boundary.  For k below the slot count a blocker request keeps the engine
    decoding while the k arrive; k = slots arrive on an idle engine.  Whether
    the group held is read from the compile events (a new admission program
    was traced); a burst that split is sent again."""
    pool = loadgen.size_pool(traffic)
    clients, slots = int(traffic["clients"]), int(warm["slots"])
    n_out = int(warm.get("n_predict", 24))
    tries = int(warm.get("tries", 3))
    rng = random.Random(f"warm:{seed}")
    report = {"bursts": [], "unconfirmed": []}

    def body(tag, n_prompt, n_predict, greedy=False):
        req = {"client": 0, "index": 0, "prompt_tokens": n_prompt,
               "n_predict": n_predict, "greedy": greedy}
        return loadgen.request_body(traffic, seed, req, vocab, tag=tag)[0]

    def admitted_since(mark: int) -> bool:
        return any(ADMIT_HINT in e["fun"] for e in events[mark:])

    def check(recs, what):
        bad = [r for r in recs if r["status"] != 200 or r["error"]]
        if bad:
            raise RuntimeError(f"warm-up {what}: {bad[0]['status']} "
                               f"{bad[0]['error']}")

    hint_seen = True
    for k in range(1, clients + 1):
        ok = False
        for attempt in range(tries):
            mark = len(events)
            sizes = [rng.choice(pool)[0] for _ in range(k)]
            bodies = [body(f"warm:{k}:{i}:{attempt}", n, n_out)
                      for i, n in enumerate(sizes)]
            blocker = None
            if 1 < k < slots:
                # keep the engine inside a decode run while the k arrive
                blocker = threading.Thread(target=lambda: check(
                    loadgen.burst(host.url, [body(
                        f"block:{k}:{attempt}", sizes[0], 6 * n_out)]),
                    "blocker"), daemon=True)
                blocker.start()
                time.sleep(float(warm.get("blocker_lead_s", 0.3)))
            check(loadgen.burst(host.url, bodies), f"burst of {k}")
            if blocker is not None:
                blocker.join()
            ok = admitted_since(mark)
            if k == 1 and not ok:
                hint_seen = False  # no such name in this program's events:
                # the groups cannot be confirmed, send each burst once
            if ok or not hint_seen:
                break
        report["bursts"].append({"rows": k, "attempts": attempt + 1,
                                 "confirmed": ok})
        if not ok:
            report["unconfirmed"].append(k)
    if not traffic.get("speculative", True):
        return report  # every request opts out: no verify program to warm
    # the speculative verify program: a greedy request that repeats itself
    lo = int(traffic["prompt_tokens"]["min"])
    ids = [(7 + j % 5) for j in range(lo)]
    rep = {"prompt": idtok.render_ids(ids), "n_predict": 3 * n_out,
           "stream": True, "temperature": 0.0, "seed": 1}
    check(loadgen.burst(host.url, [rep]), "verify")
    return report


# ------------------------------------------------------------- the window
def scale_traffic(traffic: Dict, factor: float) -> Dict:
    """A rehearsal's lengths: every token count times ``factor``."""
    out = dict(traffic)
    for key in ("prompt_tokens", "output_tokens"):
        spec = dict(traffic[key])
        for f in ("median", "min", "max", "value"):
            if f in spec:
                spec[f] = max(1, int(round(spec[f] * factor)))
        if key == "prompt_tokens":
            # still one power-of-two bucket
            bucket = 16
            while bucket < spec["max"]:
                bucket *= 2
            spec["min"] = max(spec["min"], bucket // 2 + 1)
            spec["max"] = max(spec["max"], spec["min"])
        out[key] = spec
    return out


class Session:
    """The server of one configuration, warmed for one cell's shapes: built
    once, then any number of windows (``run`` makes one; the tool that reads
    the control's limits makes a dozen, a seed each, on one set-up)."""

    def __init__(self, job: Dict):
        import jax

        from benchmark import weights as W

        self.job = job
        self.cfg, self.workload = job["cfg"], job["workload"]
        traffic = self.workload["traffic"]
        if job["rehearsal"]:
            traffic = scale_traffic(traffic,
                                    self.cfg["ctx"] / job["full_ctx"])
            traffic["settle_s"] = min(2, traffic.get("settle_s", 2))
        self.traffic = traffic
        # every trace/compile of this process, with its time: the warm-up
        # reads them to confirm its groups, a window to count what compiled
        self.events: List[Dict] = []

        def on_duration(event, duration, **kw):
            if event.endswith("jaxpr_trace_duration") or event.endswith(
                    "backend_compile_duration"):
                self.events.append({
                    "t": time.time(), "event": event.rsplit("/", 1)[-1],
                    "fun": str(kw.get("fun_name", "")), "s": duration})

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        t0 = time.time()
        self.weights = W.Weights(self.cfg, job["seed"])
        self.server = build_server(self.cfg, self.weights)
        self.t_weights = time.time() - t0
        self.host = Hosted(self.server)
        log(f"server up on {self.host.url}: weights+build "
            f"{self.t_weights:.1f}s")
        t0 = time.time()
        warm = dict(self.workload.get("warmup") or {},
                    slots=self.cfg["slots"])
        report = warm_up(self.host, traffic, job["seed"],
                         self.cfg["vocab_size"], self.events, warm)
        self.t_warm = time.time() - t0
        log(f"warm-up {self.t_warm:.1f}s: {json.dumps(report['bursts'])}")
        os.makedirs(job["out_dir"], exist_ok=True)

    def reseed(self, seed: int) -> None:
        """Another seed's weights in the same compiled programs (the tool's
        shortcut: the parameters are an argument of every program)."""
        import jax

        from benchmark import weights as W

        self.weights = W.Weights(self.cfg, seed)
        self.server.gen.params = None
        gc.collect()
        params = program_params(self.weights)
        jax.block_until_ready(params)
        self.server.gen.params = params

    def window(self, seed: int, seconds: float, trace: bool) -> Dict:
        """A child sends for ``seconds``; this process serves and, with
        ``trace``, profiles a few seconds in the middle.  Returns what the
        readers need (``ctx``), the child's records among it."""
        import jax

        job, host = self.job, self.host
        traffic_path = os.path.join(job["out_dir"], "traffic.json")
        records_path = os.path.join(job["out_dir"], "records.json")
        with open(traffic_path, "w") as f:
            json.dump({"traffic": self.traffic}, f)
        child = subprocess.Popen(
            [sys.executable,
             os.path.join(job["root"], "benchmark", "loadgen.py"),
             "--url", host.url, "--traffic", traffic_path,
             "--seed", str(seed), "--seconds", str(seconds),
             "--vocab", str(self.cfg["vocab_size"]), "--out", records_path],
            stdout=subprocess.PIPE, text=True, cwd=job["root"])
        ctx: Dict = {"cfg": self.cfg, "chips": job["chips"]}
        flight: Dict[int, Dict] = {}

        def poll_flight():
            snap = json.loads(host.get("/debug/flight?n=100000"))
            for r in snap.get("records", []):
                flight[r["seq"]] = r
            return snap

        try:
            w0, w1 = json.loads(child.stdout.readline())["window"]
            if trace:
                trace_dir = os.path.join(job["out_dir"], "trace")
                shutil.rmtree(trace_dir, ignore_errors=True)
                time.sleep(max(0.0, w0 - time.time()))
                ctx["metrics_before"] = host.get("/metrics").decode()
                trace_s = float(self.workload.get("trace_s", 3))
                time.sleep(max(0.0, (w0 + w1) / 2 - trace_s / 2
                               - time.time()))
                poll_flight()
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                a = time.time()
                time.sleep(trace_s)
                b = time.time()
                jax.profiler.stop_trace()
                ctx["trace_span"] = [a, b]
                ctx["trace_dir"] = trace_dir
            out, _ = child.communicate(timeout=(w1 - time.time()) + 240)
            if child.returncode != 0:
                raise RuntimeError(
                    f"load generator exited {child.returncode}")
            log(f"load generator: {out.strip().splitlines()[-1]}")
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        with open(records_path) as f:
            result = json.load(f)
        os.remove(records_path)
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        ctx["memory_peak_bytes"] = int(max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0))
        if trace:
            snap = poll_flight()
            ctx["metrics_after"] = host.get("/metrics").decode()
            ctx["engine_chunk"] = (snap.get("meta") or {}).get("chunk")
            ctx["flight_records"] = [flight[k] for k in sorted(flight)]
        in_window = [e for e in self.events if w0 <= e["t"] <= w1]
        if in_window:
            by: Dict[str, int] = {}
            for e in in_window:
                by[e["fun"]] = by.get(e["fun"], 0) + 1
            log(f"{len(in_window)} trace/compile events inside the window, "
                "by function: " + json.dumps(dict(sorted(
                    by.items(), key=lambda kv: -kv[1])[:12])))
        ctx.update(window=[w0, w1], records=result["records"],
                   compile_events=list(self.events),
                   stuck_clients=result.get("stuck_clients", 0))
        return ctx

    def close(self) -> None:
        """Stop the server and free the program's state: the reference runs
        after this, on an empty chip."""
        props = json.loads(self.host.get("/props"))
        log("speculation over the whole run: "
            + json.dumps(props.get("speculative") or {}))
        self.host.close()
        self.server.gen.params = None
        if self.server.paged is not None:
            self.server.paged.arrays = None
        self.server = self.host = None
        gc.collect()


def reduce_trace(ctx: Dict, describe_to: Optional[str] = None):
    """The trace of a window as ``device_busy`` (in ``ctx``) and the
    breakdown; the trace's files are removed."""
    from benchmark.readers import trace as trace_reader

    path = trace_reader.find_xplane(ctx["trace_dir"])
    devices = trace_reader.extract(path) if path else {}
    if describe_to and path:
        os.makedirs(os.path.dirname(describe_to), exist_ok=True)
        with open(describe_to, "w") as f:
            f.write("\n".join(trace_reader.describe(path)))
    ctx["devices"] = devices
    # the window is the host's span around the trace (the devices' own first
    # start to last end is as a rule shorter, which would flatter the idle
    # share), or the devices' span where starting and stopping the profiler
    # let it run past the host's: busy time never exceeds the window
    a, b = ctx["trace_span"]
    busy = trace_reader.device_busy(
        devices, max(b - a, trace_reader.span_ns(devices) / 1e9))
    breakdown = None
    if busy:
        ctx["device_busy"] = busy
        breakdown = {"device_ops": trace_reader.top_ops(devices),
                     "idle_gaps": trace_reader.idle_gaps(devices)}
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    return busy or {}, breakdown


def run(job: Dict) -> Dict:
    """One run of one cell.  ``job``: root, cfg, workload, seed, seconds,
    trace, rehearsal, full_ctx, chips, t_start, t_import, out_dir."""
    session = Session(job)
    try:
        ctx = session.window(job["seed"], job["seconds"], job["trace"])
    finally:
        session.close()
    result = {"window": ctx["window"], "records": ctx["records"],
              "stuck_clients": ctx["stuck_clients"]}
    e2e = loadgen.reduce_window(result)
    e2e["setup_s"] = ctx["window"][0] - job["t_start"]
    log(f"set-up {e2e['setup_s']:.1f}s = start+import "
        f"{job['t_import']:.1f} + weights/build {session.t_weights:.1f} + "
        f"warm-up {session.t_warm:.1f} + settle "
        f"{session.traffic.get('settle_s', 5)}; window "
        f"{json.dumps({k: v for k, v in e2e.items() if k != 'setup_s'})}")
    device_extra, breakdown = {}, None
    if job["trace"]:
        device_extra, breakdown = reduce_trace(
            ctx, os.path.join(job["root"], "chiprun_out", "trace_lines.txt")
            if job.get("describe_trace") else None)
    checks, correct = check_served(job, session.cfg, session.weights,
                                   session.workload, result, job["seed"])
    return {"attempted": e2e["attempted"], "failed": e2e["failed"],
            "correct": correct, "checks": checks, "end_to_end": e2e,
            "ctx": ctx, "memory_peak_bytes": ctx["memory_peak_bytes"],
            "device_extra": device_extra, "breakdown": breakdown}


# ----------------------------------------------------------------- correct
def pick_sample(result: Dict, seed: int, n: int) -> List[Dict]:
    """Greedy requests the window finished: the longest, and ``n - 1`` more
    drawn from the seed."""
    w0, w1 = result["window"]
    done = [r for r in result["records"]
            if r.get("greedy") and w0 <= r["t_done"] <= w1
            and r["status"] == 200 and not r["error"] and r["tokens"]]
    done.sort(key=lambda r: (r["client"], r["lap"], r["index"]))
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt_ids"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    random.Random(f"check:{seed}").shuffle(rest)
    return [longest] + rest[:max(0, n - 1)]


def stream_mismatches(result: Dict) -> int:
    """Finished requests whose streamed ids do not add up to the count the
    server reports (an EOS is counted by the server and not streamed)."""
    bad = 0
    for r in result["records"]:
        fin = r.get("final")
        if r["status"] != 200 or r["error"] or not fin:
            continue
        n = loadgen.n_tokens(r)
        want = fin.get("tokens_predicted")
        if n != want and not (fin.get("stopped_eos") and n == want - 1):
            bad += 1
    return bad


def check_served(job, cfg, weights, workload, result, seed):
    """Hold what the window served against the plain reference: the widest
    gap by which a greedily served token's reference logit lies below the
    reference's best, over a sample of finished requests."""
    from benchmark.reference import dense_gqa

    spec = workload["check"]
    if job["rehearsal"]:
        spec = dict(spec, **(spec.get("rehearsal") or {}))
    sample = pick_sample(result, seed, int(spec.get("requests", 4)))
    w0, w1 = result["window"]
    failed = sum(1 for r in result["records"]
                 if w0 <= r["t_done"] <= w1
                 and (r["status"] != 200 or r["error"]))
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "stuck_clients": {"value": result.get("stuck_clients", 0),
                          "limit": 0},
        "stream_mismatches": {"value": stream_mismatches(result),
                              "limit": 0},
        "sampled_requests": {"value": len(sample), "limit_min": 1},
    }
    if sample:
        t0 = time.time()
        seqs = [([idtok.BOS_ID] + r["prompt_ids"], r["tokens"])
                for r in sample]
        gaps = dense_gqa.served_gaps(cfg, weights, seqs)["served"]
        widest = float(max(g.max() for g in gaps))
        checks["served_tokens_compared"] = {
            "value": int(sum(len(g) for g in gaps)), "limit_min": 1}
        checks["served_gap"] = {"value": widest,
                                "limit": float(spec["served_gap_limit"])}
        checks["reference_s"] = {"value": time.time() - t0}
    correct = all(
        ("limit" not in c or c["value"] <= c["limit"])
        and ("limit_min" not in c or c["value"] >= c["limit_min"])
        for c in checks.values()) and "served_gap" in checks
    return checks, correct
