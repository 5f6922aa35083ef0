"""Multi-host LLM serving driver — SPMD lockstep over the DCN bootstrap.

The JobSet manifest (``cluster-config/apps/llm/serving-jobset.yaml``) runs
this entrypoint on every host of a multi-host slice: each process calls
``tpustack.parallel.distributed.initialize_from_env()`` (the same
COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID contract the train JobSet
uses), sees the GLOBAL device set, builds ONE tp mesh spanning all hosts
(``LLM_TP`` = total chips — e.g. 16 over 2 × v5e-8, lifting the model-size
ceiling past a single host's HBM), and serves a fixed prompt fleet through
``Generator.generate_batch`` with XLA's collectives riding ICI within a
host and DCN across.

Why ``generate_batch`` and not the continuous engine: multi-controller JAX
requires every process to dispatch the SAME programs in the SAME order.
``generate_batch``'s control flow is a pure function of (prompts, budgets,
fetched tokens) — and fetched tokens are replicated device values, so all
ranks take identical branches without any cross-host coordination.  The
continuous engine's loop is NOT rank-deterministic (``is_ready()`` polling,
wall-clock admission timing), so online multi-host continuous serving
additionally needs a rank-0 → followers request broadcast at its feed/
cancel points — the ROADMAP follow-up this driver de-risks.  Until then
this is the batch/offline serving form: prompts from ``LLM_MULTIHOST_
PROMPTS`` (one per line; a synthetic fleet when unset), results written by
rank 0 only.

Single-process (no JobSet env) it degrades to a plain one-host batch
serving run — which is what the tier-1 CPU test drives; the 2-process DCN
path mirrors ``tests/test_distributed_bootstrap.py``'s slow tier.
"""

from __future__ import annotations

import json
import sys
import time

from tpustack.utils import get_logger, knobs

log = get_logger("serving.llm_multihost")


def _load_prompts(tok, path: str, batch: int):
    """Prompt texts → token id lists, identical on every rank (the file is
    read deterministically; the synthetic fallback is seed-free)."""
    if path:
        with open(path) as f:
            texts = [ln.rstrip("\n") for ln in f if ln.strip()]
    else:
        texts = [f"multihost serving rehearsal prompt {i} "
                 f"{'lorem ipsum ' * 4}" for i in range(batch)]
    ids = [tok.encode(t) for t in texts]
    return [(t, i) for t, i in zip(texts, ids) if i]


def run(argv=None) -> int:
    import jax

    from tpustack.parallel.distributed import initialize_from_env
    from tpustack.utils import enable_compile_cache, require_accelerator

    multi = initialize_from_env()  # before the guard touches the backend
    require_accelerator()
    enable_compile_cache()
    rank = jax.process_index() if multi else 0
    log.info("llm_multihost: %d process(es), rank %d, %d global device(s)",
             jax.process_count() if multi else 1, rank, jax.device_count())

    from tpustack.models.llm_generate import SampleConfig
    from tpustack.serving.llm_server import _build_generator

    # _build_generator reads LLM_PRESET/LLM_CTX/LLM_TP &co and builds the
    # tp mesh over the GLOBAL device list — under jax.distributed that
    # spans every host, which is the whole point of this entrypoint
    gen, tok, preset = _build_generator()
    batch = max(1, knobs.get_int("LLM_MAX_BATCH"))
    new_tokens = max(1, knobs.get_int("LLM_MULTIHOST_NEW_TOKENS"))
    prompts = _load_prompts(tok, knobs.get_str("LLM_MULTIHOST_PROMPTS"),
                            batch)
    if not prompts:
        log.error("no prompts to serve")
        return 1

    sample = SampleConfig(greedy=True)  # deterministic across ranks
    results = []
    t0 = time.time()
    for lo in range(0, len(prompts), batch):
        chunk = prompts[lo:lo + batch]
        outs, stats = gen.generate_batch(
            [ids for _, ids in chunk], new_tokens,
            [sample] * len(chunk), seed=0, stop_tokens=(tok.eos_id,))
        for (text, _), out in zip(chunk, outs):
            if out and out[-1] == tok.eos_id:
                out = out[:-1]
            results.append({"prompt": text, "content": tok.decode(out),
                            "generated_tokens": len(out)})
        log.info("batch %d: %d rows, %.1f tok/s aggregate",
                 lo // batch, len(chunk), stats["tokens_per_s"])
    wall = time.time() - t0

    if rank == 0:
        n_tok = sum(r["generated_tokens"] for r in results)
        print(json.dumps({
            "preset": preset,
            "processes": jax.process_count() if multi else 1,
            "devices": jax.device_count(),
            "tp": int(gen.mesh.shape["tp"]) if gen.mesh is not None else 1,
            "requests": len(results),
            "generated_tokens": n_tok,
            "tokens_per_s": round(n_tok / wall, 2) if wall > 0 else 0.0,
            "results": results,
        }), flush=True)
    return 0


def main() -> None:
    from tpustack.obs.http import maybe_start_metrics_sidecar

    maybe_start_metrics_sidecar()  # TPUSTACK_METRICS_PORT, JobSet-scraped
    sys.exit(run())


if __name__ == "__main__":
    main()
