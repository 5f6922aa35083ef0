"""Training-ladder task CLIs (the BASELINE.json configs, run by the k8s Jobs
in ``cluster-config/jobs/``):

    python -m tpustack.train.tasks resnet50 --steps 100 --batch 256
    python -m tpustack.train.tasks bert     --steps 200 --batch 64 --dp 8
    python -m tpustack.train.tasks llama2   --steps 100 --batch 16 --fsdp 8 --tp 2

Each task: synthetic data (the reference ships no datasets; throughput is the
metric), the shared sharded train step, preemption-safe Orbax
checkpoint/resume via ``tpustack.train.resilience`` (async atomic saves,
integrity-verified restore with corrupt-step quarantine, SIGTERM →
emergency checkpoint → resumable exit 42 — see docs/RESILIENCE.md
"Training"), and a steps/sec + examples/sec report on stdout.  ``llama2``
initialises ``jax.distributed`` from JobSet env when NUM_PROCESSES>1.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpustack.obs import trace as obs_trace
from tpustack.train import resilience
from tpustack.utils import get_logger

log = get_logger("train.tasks")


def _report(step: int, metrics: Dict[str, Any], t0: float, n_done: int,
            batch: int) -> None:
    dt = time.time() - t0
    log.info("step=%d loss=%.4f steps/s=%.3f examples/s=%.1f",
             step, float(metrics["loss"]), n_done / dt, n_done * batch / dt)


def _state_step(state) -> int:
    return int(state["step"] if isinstance(state, dict) else state.step)


def _maybe_restore(ckpt_dir: Optional[str], state, save_every: int = 50,
                   task: str = "train"):
    """Build the resilient checkpointer and restore the newest checkpoint
    that passes integrity verification (corrupt steps are quarantined, an
    empty/partially-written directory is a fresh start, never a crash)."""
    if not ckpt_dir:
        return state, None
    ckpt = resilience.ResilientCheckpointer(ckpt_dir, task=task,
                                            save_every=save_every)
    shardings = jax.tree.map(lambda x: getattr(x, "sharding", None), state)
    restored, latest = ckpt.restore_latest(state)
    if restored is not None:
        # orbax does not re-apply every leaf's sharding (scalars come back on
        # one device); re-place so the jitted step sees a consistent mesh
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s is not None else x,
            restored, shardings)
        log.info("Resumed from checkpoint step %d", latest)
    return state, ckpt


def _train_loop(state, ckpt, step, make_batch, args, task: str = "train") -> Any:
    """The shared step loop: resume-deterministic data (per-step seeded),
    per-step rng (``fold_in`` — tasks whose loss samples noise must see
    FRESH randomness each step), periodic report, async checkpointing with
    a barrier on every exit path, and preemption-aware emergency saves.

    At each step boundary (``i`` steps complete): fire the injected kill
    if armed, then honour a pending SIGTERM — flush an emergency
    checkpoint of the current state and exit ``EXIT_PREEMPTED``.  The
    resumed run restores exactly ``i`` steps and replays the identical
    data/rng stream, so an interrupted run is bitwise-identical to an
    uninterrupted one (``tools/chaos_train.py`` asserts this)."""
    rng = jax.random.PRNGKey(2)
    t0 = None
    start = _state_step(state)
    guard = resilience.get_guard()
    try:
        for i in range(start, args.steps):
            if ckpt is not None:
                ckpt.fault.maybe_kill(i)
            if guard is not None and guard.requested:
                if ckpt is not None and jax.process_count() == 1:
                    ckpt.emergency_save(i, state)
                    log.warning("emergency checkpoint step=%d — exiting %d "
                                "(resumable)", i, resilience.EXIT_PREEMPTED)
                elif ckpt is not None:
                    # orbax saves are COLLECTIVE in a multi-process run: a
                    # one-sided save from the preempted worker would hang at
                    # the cross-process barrier until SIGKILL.  Exit
                    # promptly; the JobSet restart resumes the whole set
                    # from the last periodic checkpoint.
                    log.warning("preempted at step=%d in a %d-process run — "
                                "skipping the (collective) emergency save, "
                                "resuming from the last periodic checkpoint; "
                                "exiting %d", i, jax.process_count(),
                                resilience.EXIT_PREEMPTED)
                else:
                    log.warning("preempted at step=%d with no --ckpt-dir "
                                "(nothing to save) — exiting %d", i,
                                resilience.EXIT_PREEMPTED)
                raise resilience.Preempted(i)
            # per-step trace (root span per step, process-wide tracer): the
            # TPUSTACK_METRICS_PORT sidecar serves these on /debug/traces,
            # so "which step stalled" is answerable without a debugger.
            # Covers batch build + the step dispatch — async dispatch means
            # device time shows up in whichever step the host next syncs in
            with obs_trace.TRACER.span("train_step", parent=None,
                                       task=task, step=i):
                batch = make_batch(np.random.RandomState(i))
                state, metrics = step(state, batch,
                                      jax.random.fold_in(rng, i))
            if i == start:
                # intended sync: the compile barrier — steps/s must not
                # amortise the first step's trace+compile time
                jax.block_until_ready(metrics["loss"])  # tpulint: disable=TPL101
                t0 = time.time()
            elif (i + 1) % 10 == 0 or i == args.steps - 1:
                # intended sync: once per 10 steps for the progress report
                # (the only fetch in the steady-state step chain)
                jax.block_until_ready(metrics["loss"])  # tpulint: disable=TPL101
                _report(i + 1, metrics, t0, i - start, args.batch)
            resilience.beat(task)
            if ckpt is not None:
                ckpt.save(i + 1, state, force=i == args.steps - 1)
                ckpt.poll()
    except BaseException:
        # the barrier must run on EVERY exit path (an exception between the
        # last save and the barrier would strand an uncommitted checkpoint)
        # but a secondary flush error must not mask the real one
        if ckpt is not None:
            ckpt.finalize(raise_errors=False)
        raise
    if ckpt is not None:
        ckpt.finalize(raise_errors=True)
    return state, start


# --------------------------------------------------------------------- tasks

def run_sd15(args) -> None:
    """SD1.5 UNet fine-tune: DDPM epsilon-prediction MSE, dp-sharded.

    The diffusion-training counterpart of the serving flagship (reference
    trains nothing — SURVEY.md §2.10): noise a latent with the forward
    process at a random timestep, predict the noise, MSE.  Text/VAE towers
    stay frozen (standard SD fine-tune).  ``--export-dir`` writes the result
    through the diffusers-layout safetensors writer, so ``sd_server``
    (``MODEL_DIR``) serves it directly — the train→serve loop of
    ``tests/test_real_weight_e2e.py`` as an operable k8s Job.
    """
    from jax.sharding import PartitionSpec as PS

    from tpustack.models.sd15 import SD15Config, SD15Pipeline
    from tpustack.models.sd15.scheduler import NUM_TRAIN_TIMESTEPS, add_noise
    from tpustack.parallel import build_mesh
    from tpustack.parallel.sharding import BATCH_SPEC
    from tpustack.train.trainer import (TrainerConfig, make_sharded_train_step,
                                        make_train_state)

    import os

    dtype = "bfloat16" if args.bf16 else "float32"
    cfg = (SD15Config.tiny(dtype=dtype) if args.tiny
           else SD15Config.sd15(dtype=dtype))
    pipe = SD15Pipeline(cfg)
    model_dir = os.environ.get("MODEL_DIR", "")
    if model_dir:  # fine-tune FROM a checkpoint (same env contract as serving)
        from tpustack.models.sd15.weights import load_sd15_safetensors

        pipe.params = load_sd15_safetensors(model_dir, cfg, pipe.params)
    lat = 8 if args.tiny else 64  # latent side: 64 ↔ the 512x512 serving shape
    ctx_dim = cfg.unet.cross_attention_dim

    dp = args.dp or len(jax.devices())
    mesh = build_mesh((dp, 1, 1, 1), devices=jax.devices()[:dp])
    rules = ((r".*", PS()),)  # DP fine-tune: replicate params, shard batch

    def make_batch(rng):
        return {
            "x0": jnp.asarray(rng.randn(args.batch, lat, lat,
                                        cfg.unet.in_channels), jnp.float32),
            "ctx": jnp.asarray(rng.randn(args.batch, cfg.text.max_length,
                                         ctx_dim), jnp.float32),
            "t": jnp.asarray(rng.randint(0, NUM_TRAIN_TIMESTEPS,
                                         (args.batch,)), jnp.int32),
        }

    def loss_fn(params, batch, rng):
        noise = jax.random.normal(rng, batch["x0"].shape)
        x_t = add_noise(batch["x0"], noise, batch["t"])
        eps = pipe.unet.apply({"params": params},
                              x_t.astype(cfg.compute_dtype), batch["t"],
                              batch["ctx"].astype(cfg.compute_dtype))
        return jnp.mean((eps.astype(jnp.float32) - noise) ** 2)

    tcfg = TrainerConfig(learning_rate=args.lr, remat=args.remat)
    state, _ = make_train_state(pipe.params["unet"], tcfg, mesh=mesh,
                                rules=rules)
    state, ckpt = _maybe_restore(args.ckpt_dir, state, args.save_every,
                                 task="sd15")
    step = make_sharded_train_step(loss_fn, tcfg, mesh=mesh,
                                   batch_spec=BATCH_SPEC)
    state, start = _train_loop(state, ckpt, step, make_batch, args,
                               task="sd15")

    if args.export_dir:
        from tpustack.models.sd15.weights import save_sd15_safetensors

        pipe.params = dict(pipe.params,
                           unet=jax.device_get(state.params))
        save_sd15_safetensors(args.export_dir, cfg, pipe.params)
        log.info("Exported servable snapshot to %s (point MODEL_DIR at it)",
                 args.export_dir)
    log.info("sd15 done: %d steps on mesh %s", args.steps - start,
             dict(zip(mesh.axis_names, mesh.devices.shape)))


def run_resnet50(args) -> None:
    """Config #3: ResNet-50, 1 chip.  BatchNorm stats threaded explicitly
    through a dict state so the shared resilient loop checkpoints them."""
    import optax

    from tpustack.models.resnet import ResNet50
    from tpustack.train.trainer import TrainerConfig, make_optimizer

    # --tiny: one bottleneck block per stage, two stages — the chaos/CI
    # config (tools/chaos_train.py, tests/test_train_resilience.py): full
    # ResNet-50 compiles for ~30s on CPU, this compiles in ~2s
    stage_sizes = (1, 1) if args.tiny else (3, 4, 6, 3)
    model = ResNet50(num_classes=args.classes, stage_sizes=stage_sizes,
                     dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    size = args.image_size
    rng = jax.random.PRNGKey(0)
    fake = jnp.zeros((args.batch, size, size, 3), jnp.float32)
    variables = jax.jit(model.init, static_argnums=(2,))(rng, fake, True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tcfg = TrainerConfig(learning_rate=args.lr)
    opt = make_optimizer(tcfg)

    # Checkpoint/resume: the k8s Job mounts /ckpt on a durable volume and
    # passes --ckpt-dir (cluster-config/jobs/train-resnet50.yaml); a pod
    # restart (backoffLimit) continues from the latest verified step.
    state = {"step": jnp.zeros((), jnp.int32), "params": params,
             "batch_stats": batch_stats, "opt_state": opt.init(params)}
    state, ckpt = _maybe_restore(args.ckpt_dir, state, args.save_every,
                                 task="resnet50")

    @jax.jit
    def step_fn(state, batch, rng):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": state["batch_stats"]},
                batch["images"], True, mutable=["batch_stats"])
            onehot = jax.nn.one_hot(batch["labels"], args.classes)
            loss = optax.softmax_cross_entropy(logits, onehot).mean()
            return loss, mut["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        updates, opt_state = opt.update(grads, state["opt_state"],
                                        state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"step": state["step"] + 1, "params": params,
                "batch_stats": new_stats, "opt_state": opt_state}, \
            {"loss": loss}

    def make_batch(data_rng):
        # per-step seed so a resumed run continues the exact data stream an
        # uninterrupted run would have seen
        return {"images": jnp.asarray(data_rng.rand(args.batch, size, size, 3),
                                      jnp.float32),
                "labels": jnp.asarray(data_rng.randint(0, args.classes,
                                                       args.batch))}

    state, start = _train_loop(state, ckpt, step_fn, make_batch, args,
                               task="resnet50")
    log.info("resnet50 done: %d steps", args.steps - start)


def _generic_lm_task(args, kind: str) -> None:
    """Configs #4/#5: BERT DP and Llama-2 FSDP+TP via the shared machinery."""
    from jax.sharding import PartitionSpec as PS

    from tpustack.parallel import build_mesh
    from tpustack.parallel.sharding import BATCH_SPEC, LLAMA_RULES
    from tpustack.train.trainer import (TrainerConfig, make_sharded_train_step,
                                        make_train_state)

    n_dev = len(jax.devices())
    if kind == "bert":
        from tpustack.models.bert import BertClassifier, BertConfig

        cfg = BertConfig.tiny() if args.tiny else BertConfig.base()
        model = BertClassifier(cfg, dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
        seq = args.seq or 128
        rules = ((r".*", PS()),)  # DP fine-tune: replicate params, shard the batch
        dp = args.dp or n_dev
        mesh = build_mesh((dp, 1, 1, 1))

        def make_batch(rng):
            ids = rng.randint(0, cfg.vocab_size, (args.batch, seq))
            mask = np.ones((args.batch, seq), np.int32)
            labels = rng.randint(0, cfg.num_classes, (args.batch,))
            return {"ids": jnp.asarray(ids), "mask": jnp.asarray(mask),
                    "labels": jnp.asarray(labels)}

        def loss_fn(params, batch, rng):
            import optax

            logits = model.apply({"params": params}, batch["ids"], batch["mask"])
            onehot = jax.nn.one_hot(batch["labels"], cfg.num_classes)
            return optax.softmax_cross_entropy(logits, onehot).mean()

        init_batch = make_batch(np.random.RandomState(0))
        params = jax.jit(model.init)(jax.random.PRNGKey(0), init_batch["ids"],
                                     init_batch["mask"])["params"]
    elif kind == "llama2" and args.pp > 1:
        # pipeline-parallel variant: layers cut over a pp mesh axis (GPipe,
        # parallel/pipeline.py); dp shards the batch; tp/sp stay 1 inside
        # the pipeline (manual-mode shard_map)
        from tpustack.models.llama import LlamaConfig
        from tpustack.models.llama_pipeline import PipelinedLlamaLM
        from tpustack.parallel.sharding import LLAMA_PP_RULES

        cfg = LlamaConfig.tiny() if args.tiny else LlamaConfig.llama2_7b()
        seq = args.seq or min(cfg.max_seq, 2048)
        pp = args.pp
        if args.tp > 1 or args.sp > 1 or args.fsdp > 1:
            raise SystemExit("--pp composes with --dp only (tp/sp/fsdp are 1 "
                             "inside a pipeline stage — shard_map is manual "
                             "mode)")
        if n_dev % pp:
            raise SystemExit(f"--pp={pp} must divide the {n_dev} devices")
        dp = args.dp or (n_dev // pp)
        if dp * pp != n_dev:
            raise SystemExit(f"--dp={dp} x --pp={pp} != {n_dev} devices")
        mesh = build_mesh((dp, 1, 1, 1, pp),
                          axis_names=("dp", "fsdp", "tp", "sp", "pp"))
        rules = LLAMA_PP_RULES
        # default microbatches: 2*pp (bubble fraction (pp-1)/(M+pp-1)),
        # shrunk until each microbatch still divides over the dp shards; an
        # EXPLICIT --microbatches is honoured or rejected, never adjusted
        microbatches = args.microbatches or max(2, 2 * pp)
        if not args.microbatches:
            while (microbatches > 2
                   and (args.batch % microbatches
                        or (args.batch // microbatches) % dp)):
                microbatches -= 1
        if args.batch % microbatches or (args.batch // microbatches) % dp:
            raise SystemExit(
                f"--batch={args.batch} cannot be cut into {microbatches} "
                f"microbatches of a multiple of dp={dp} rows")
        pl = PipelinedLlamaLM(cfg, mesh, microbatches=microbatches,
                              dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
                              remat=args.remat)
        # per-layer remat inside the pipeline already bounds activations;
        # also wrapping the whole loss would re-run the full GPipe forward
        # (all ICI hops) a second time in backward
        args.remat = False

        def make_batch(rng):
            return jnp.asarray(rng.randint(0, cfg.vocab_size, (args.batch, seq)))

        def loss_fn(params, batch, rng):
            return pl.loss(params, batch)

        params = pl.init(jax.random.PRNGKey(0))
    else:  # llama2
        from tpustack.models.llama import LlamaConfig, LlamaModel, causal_lm_loss

        cfg = LlamaConfig.tiny() if args.tiny else LlamaConfig.llama2_7b()
        seq = args.seq or min(cfg.max_seq, 2048)
        rules = LLAMA_RULES
        tp = args.tp or 1
        sp = args.sp or 1
        if n_dev % (tp * sp) or n_dev < tp * sp:
            raise SystemExit(
                f"--tp={tp} x --sp={sp} must divide the {n_dev} devices")
        fsdp = args.fsdp or (n_dev // (tp * sp))
        if n_dev % (tp * sp * fsdp):
            raise SystemExit(
                f"--tp={tp} x --sp={sp} x --fsdp={fsdp} must divide the "
                f"{n_dev} devices")
        dp = n_dev // (tp * sp * fsdp)
        mesh = build_mesh((dp, fsdp, tp, sp))
        model = LlamaModel(cfg, dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
                           ring_mesh=mesh if sp > 1 else None)

        def make_batch(rng):
            return jnp.asarray(rng.randint(0, cfg.vocab_size, (args.batch, seq)))

        def loss_fn(params, batch, rng):
            logits, _ = model.apply({"params": params}, batch)
            return causal_lm_loss(logits, batch)

        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    tcfg = TrainerConfig(learning_rate=args.lr, remat=args.remat)
    state, specs = make_train_state(params, tcfg, mesh=mesh, rules=rules)
    state, ckpt = _maybe_restore(args.ckpt_dir, state, args.save_every,
                                 task=kind)
    step = make_sharded_train_step(loss_fn, tcfg, mesh=mesh,
                                   batch_spec=BATCH_SPEC)
    state, start = _train_loop(state, ckpt, step, make_batch, args, task=kind)
    log.info("%s done: %d steps on mesh %s", kind, args.steps - start,
             dict(zip(mesh.axis_names, mesh.devices.shape)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="tpustack training ladder")
    p.add_argument("task", choices=["resnet50", "bert", "llama2", "sd15"])
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--seq", type=int, default=0)
    p.add_argument("--dp", type=int, default=0)
    p.add_argument("--fsdp", type=int, default=0)
    p.add_argument("--tp", type=int, default=0)
    p.add_argument("--sp", type=int, default=0,
                   help="sequence-parallel ways (llama2): >1 rings K/V over "
                        "the sp axis for long-context training")
    p.add_argument("--pp", type=int, default=0,
                   help="pipeline-parallel stages (llama2): layers cut over "
                        "a pp mesh axis, GPipe microbatch schedule")
    p.add_argument("--microbatches", type=int, default=0,
                   help="pipeline microbatches (default 2*pp; batch must "
                        "divide)")
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--bf16", action="store_true", default=True)
    p.add_argument("--no-bf16", dest="bf16", action="store_false")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--tiny", action="store_true",
                   help="tiny model config (CI / smoke / chaos harness)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--save-every", type=int, default=50,
                   help="checkpoint save interval in steps")
    p.add_argument("--export-dir", default="",
                   help="sd15: write the fine-tuned model as a diffusers "
                        "snapshot servable via MODEL_DIR")
    args = p.parse_args(argv)

    from tpustack.parallel.distributed import initialize_from_env
    from tpustack.utils import enable_compile_cache, require_accelerator

    # no-op single-process; JobSet env multi-host — and it must run before
    # the guard (or anything else) initialises the backend
    initialize_from_env()
    require_accelerator()
    enable_compile_cache()  # restarted/rescheduled trainers skip cold jit

    # TPUSTACK_METRICS_PORT (the train-job manifests set 9100): stdlib
    # /metrics sidecar thread so Prometheus sees trainer device gauges —
    # jobs are not aiohttp apps, so this is their only exposition path
    from tpustack.obs import device as obs_device
    from tpustack.obs.http import maybe_start_metrics_sidecar

    obs_device.install()
    maybe_start_metrics_sidecar()

    # Preemption guard: SIGTERM → emergency checkpoint at the next step
    # boundary → exit EXIT_PREEMPTED (42), which the Job's restart budget
    # turns into a resume (docs/RESILIENCE.md "Training")
    resilience.install_preemption_guard()

    if args.task == "resnet50":
        run_resnet50(args)
    elif args.task == "sd15":
        run_sd15(args)
    else:
        _generic_lm_task(args, args.task)
    return 0


if __name__ == "__main__":
    sys.exit(main())
