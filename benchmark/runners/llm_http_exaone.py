"""Runner: ``llm_http`` for the ``exaone_moe`` family (K-EXAONE).

Everything that drives and measures — the hosted server, the warm-up, the
window and its trace, the sample and the stream check — is ``llm_http``'s,
imported.  This module supplies only what is hard-wired there to the dense
family: the model's spec, the parameter tree the program reads, the seeded
weights (``benchmark/weights_exaone``) and a ``check_served`` that calls the
reference the configuration names (``cfg["reference"]``).  A rehearsal
applies ``tests/rehearsal_exaone.json`` on top of ``tests/rehearsal.json``.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import sys
import time
from typing import Dict

from benchmark import idtok, loadgen
from benchmark import weights_exaone as WX
from benchmark.runners import llm_http
from benchmark.runners.llm_http import log


# ------------------------------------------------------------ the program
def model_spec(cfg: Dict):
    """The configuration as the program's ``LlamaConfig``."""
    from tpustack.models.llama import LayerSpec, LlamaConfig, MoESpec

    m = WX.dims(cfg)
    layers = tuple(
        LayerSpec(window=w, rope=w is not None,
                  ffn="experts" if sparse else "dense")
        for w, sparse in zip(m["windows"], m["sparse"]))
    return LlamaConfig(
        vocab_size=m["vocab"], dim=m["d"], n_layers=m["layers"],
        n_heads=m["h"], n_kv_heads=m["kvh"], head_size=m["hd"],
        ffn_dim=m["ffn"], max_seq=cfg["ctx"], rope_theta=m["theta"],
        rms_eps=m["eps"], layers=layers, norm_placement="post",
        qk_norm=True,
        quant="int8" if cfg.get("weights") == "int8" else None,
        kv_quant="int8" if cfg.get("kv") == "int8" else None,
        moe=MoESpec(n_experts=m["n_router"], top_k=m["top_k"],
                    expert_dim=m["eff"], shared_dim=m["sff"],
                    routed_scale=m["routed_scale"],
                    held=(m["first"], m["held"])))


def program_params(weights: "WX.Weights") -> Dict:
    """The benchmark's weights in the tree ``LlamaModel`` reads."""

    def dense(w):
        return ({"kernel": w["q"], "scale": w["s"]} if isinstance(w, dict)
                else {"kernel": w})

    def swiglu(w, names):
        return {k: dense(w[n]) for k, n in zip(
            ("gate_proj", "up_proj", "down_proj"), names)}

    emb = weights.embed()
    tree = {"embed_tokens": ({"embedding": emb["q"], "scale": emb["s"]}
                            if isinstance(emb, dict) else {"embedding": emb}),
            "norm": {"scale": weights.final_norm()},
            "lm_head": dense(weights.head())}
    for i in range(weights.n_layers):
        w = weights.layer(i)
        if weights.m["sparse"][i]:
            mlp = dict(swiglu(w, WX.EXPERT), router=w["router"],
                       score_bias=w["bias"], shared=swiglu(w, WX.SHARED))
        else:
            mlp = swiglu(w, WX.DENSE)
        tree[f"layers_{i}"] = {
            "post_attention_layernorm": {"scale": w["ln1"]},
            "post_feedforward_layernorm": {"scale": w["ln2"]},
            "self_attn": {"q_proj": dense(w["wq"]), "k_proj": dense(w["wk"]),
                          "v_proj": dense(w["wv"]), "o_proj": dense(w["wo"]),
                          "q_norm": {"scale": w["qn"]},
                          "k_norm": {"scale": w["kn"]}},
            "mlp": mlp}
    return tree


def build_server(cfg: Dict, weights):
    import logging

    import jax
    import jax.numpy as jnp

    from tpustack.models.llm_generate import Generator
    from tpustack.serving.llm_server import LLMServer

    for k, v in (cfg.get("env") or {}).items():
        os.environ[k] = str(v)
    for h in logging.getLogger("tpustack").handlers:
        if getattr(h, "stream", None) is sys.stdout:
            h.setStream(sys.stderr)  # stdout ends with the result line
    spec = model_spec(cfg)  # first: a program without layer kinds stops here
    params = program_params(weights)
    jax.block_until_ready(params)
    gen = Generator(spec, params=params, dtype=jnp.bfloat16)
    del params
    return LLMServer(generator=gen,
                     tokenizer=idtok.IdTokenizer(cfg["vocab_size"]),
                     model_name=cfg["name"], max_batch=int(cfg["slots"]))


class Session(llm_http.Session):
    """``llm_http.Session`` with this family's weights and server; the
    window, the trace and the shutdown are the parent's."""

    def __init__(self, job: Dict):
        import jax

        self.job = job
        self.cfg, self.workload = job["cfg"], job["workload"]
        traffic = self.workload["traffic"]
        if job["rehearsal"]:
            traffic = llm_http.scale_traffic(
                traffic, self.cfg["ctx"] / job["full_ctx"])
            traffic["settle_s"] = min(2, traffic.get("settle_s", 2))
        self.traffic = traffic
        self.events = []

        def on_duration(event, duration, **kw):
            if event.endswith("jaxpr_trace_duration") or event.endswith(
                    "backend_compile_duration"):
                self.events.append({
                    "t": time.time(), "event": event.rsplit("/", 1)[-1],
                    "fun": str(kw.get("fun_name", "")), "s": duration})

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        t0 = time.time()
        self.weights = WX.Weights(self.cfg, job["seed"])
        self.server = build_server(self.cfg, self.weights)
        self.t_weights = time.time() - t0
        self.host = llm_http.Hosted(self.server)
        log(f"server up on {self.host.url}: weights+build "
            f"{self.t_weights:.1f}s")
        t0 = time.time()
        warm = dict(self.workload.get("warmup") or {},
                    slots=self.cfg["slots"])
        report = llm_http.warm_up(self.host, traffic, job["seed"],
                                  self.cfg["vocab_size"], self.events, warm)
        self.t_warm = time.time() - t0
        log(f"warm-up {self.t_warm:.1f}s: {json.dumps(report['bursts'])}")
        os.makedirs(job["out_dir"], exist_ok=True)

    def reseed(self, seed: int) -> None:
        import jax

        self.weights = WX.Weights(self.cfg, seed)
        self.server.gen.params = None
        gc.collect()
        params = program_params(self.weights)
        jax.block_until_ready(params)
        self.server.gen.params = params


def rehearsal_cfg(job: Dict) -> Dict:
    """The family's own rehearsal sizes over the shared ones."""
    with open(os.path.join(job["root"], "benchmark", "tests",
                           "rehearsal_exaone.json")) as f:
        return dict(job["cfg"], **json.load(f))


def run(job: Dict) -> Dict:
    """One run of one cell: ``llm_http.run`` with this module's session and
    check."""
    if job["rehearsal"]:
        job = dict(job, cfg=rehearsal_cfg(job))
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
        device_extra, breakdown = llm_http.reduce_trace(
            ctx, os.path.join(job["root"], "chiprun_out", "trace_lines.txt")
            if job.get("describe_trace") else None)
    checks, correct = check_served(job, session.cfg, session.weights,
                                   session.workload, result, job["seed"])
    return {"attempted": e2e["attempted"], "failed": e2e["failed"],
            "correct": correct, "checks": checks, "end_to_end": e2e,
            "ctx": ctx, "memory_peak_bytes": ctx["memory_peak_bytes"],
            "device_extra": device_extra, "breakdown": breakdown}


# ----------------------------------------------------------------- correct
def check_served(job, cfg, weights, workload, result, seed):
    """``llm_http.check_served`` with the reference the configuration
    names: the widest gap by which a greedily served token's reference
    logit lies below the reference's best, over a sample of requests."""
    reference = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    spec = workload["check"]
    if job["rehearsal"]:
        spec = dict(spec, **(spec.get("rehearsal") or {}))
    sample = llm_http.pick_sample(result, seed, int(spec.get("requests", 4)))
    w0, w1 = result["window"]
    failed = sum(1 for r in result["records"]
                 if w0 <= r["t_done"] <= w1
                 and (r["status"] != 200 or r["error"]))
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "stuck_clients": {"value": result.get("stuck_clients", 0),
                          "limit": 0},
        "stream_mismatches": {"value": llm_http.stream_mismatches(result),
                              "limit": 0},
        "sampled_requests": {"value": len(sample), "limit_min": 1},
    }
    if sample:
        t0 = time.time()
        seqs = [([idtok.BOS_ID] + r["prompt_ids"], r["tokens"])
                for r in sample]
        gaps = reference.served_gaps(cfg, weights, seqs)["served"]
        checks["served_tokens_compared"] = {
            "value": int(sum(len(g) for g in gaps)), "limit_min": 1}
        checks["served_gap"] = {"value": float(max(g.max() for g in gaps)),
                                "limit": float(spec["served_gap_limit"])}
        checks["reference_s"] = {"value": time.time() - t0}
    correct = all(
        ("limit" not in c or c["value"] <= c["limit"])
        and ("limit_min" not in c or c["value"] >= c["limit_min"])
        for c in checks.values()) and "served_gap" in checks
    return checks, correct
