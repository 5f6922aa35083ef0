#!/usr/bin/env python
"""Chaos-restart harness: prove a preempted training run resumes EXACTLY.

Runs a tiny training task to completion (the reference run), then runs the
same task again but kills it at K random step boundaries — each kill is a
*real* SIGTERM delivered by ``TPUSTACK_FAULT_TRAIN_KILL_STEP`` — resuming
from the emergency checkpoint after every kill.  At the end it asserts the
final checkpoint (params, optimizer state, batch stats, step) is
**bitwise-identical** to the uninterrupted run's: the per-step-seeded data
and per-step ``fold_in`` rng in ``tpustack.train.tasks`` make training a
pure function of the step index, and this harness proves the
checkpoint/restore layer preserves that end to end.

    python tools/chaos_train.py              # 3 kills over 12 steps
    python tools/chaos_train.py --fast       # 1 kill over 6 steps (tier-1)
    python tools/chaos_train.py --seed 7 --kills 5 --steps 20

Exit 0 = every kill produced ``emergency checkpoint step=N`` + exit 42,
every restart logged ``Resumed from checkpoint step N``, and the final
parameters match bit for bit.  Any other outcome exits 1 with diagnostics.
"""

from __future__ import annotations

import argparse
import os
import random
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpustack.train.resilience import EXIT_PREEMPTED  # noqa: E402

#: the tiny-resnet chaos config: ~2s compile on CPU, checkpoints every
#: 2 steps so kills land between save boundaries too
TASK_ARGV = ["resnet50", "--tiny", "--batch", "2", "--classes", "4",
             "--image-size", "16", "--no-bf16", "--save-every", "2"]


def run_task(ckpt_dir: str, steps: int, kill_step: int = 0):
    # a CPU drill by construction: kill/resume parity is asserted bitwise
    # against a CPU reference run, and no child is pinned to a chip
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TPUSTACK_FAULT_TRAIN_KILL_STEP", None)
    env.pop("TPUSTACK_FAULT_TRAIN_CORRUPT_CKPT", None)
    if kill_step:
        env["TPUSTACK_FAULT_TRAIN_KILL_STEP"] = str(kill_step)
    cmd = ([sys.executable, "-m", "tpustack.train.tasks"] + TASK_ARGV
           + ["--steps", str(steps), "--ckpt-dir", ckpt_dir])
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO)


def load_final(ckpt_dir: str, step: int):
    import orbax.checkpoint as ocp

    mngr = ocp.CheckpointManager(ckpt_dir)
    if mngr.latest_step() != step:
        raise AssertionError(
            f"{ckpt_dir}: latest step {mngr.latest_step()} != {step}")
    # template-free restore: orbax warns it can't check the topology, but
    # for a bitwise A/B comparison the raw on-disk trees are exactly what
    # we want
    return mngr.restore(step, args=ocp.args.StandardRestore())


def trees_bitwise_equal(a, b) -> list:
    """Return the list of leaf paths that differ (empty = identical)."""
    import jax
    import numpy as np

    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    if ta != tb:
        return ["<tree structure differs>"]
    diffs = []
    for (path, xa), (_, xb) in zip(la, lb):
        na, nb = np.asarray(xa), np.asarray(xb)
        if na.dtype != nb.dtype or na.shape != nb.shape \
                or na.tobytes() != nb.tobytes():
            diffs.append(jax.tree_util.keystr(path))
    return diffs


def main() -> int:
    p = argparse.ArgumentParser(
        description="kill/resume chaos harness for the training ladder")
    p.add_argument("--kills", type=int, default=3,
                   help="number of kill/resume cycles")
    p.add_argument("--steps", type=int, default=12, help="total train steps")
    p.add_argument("--seed", type=int, default=0,
                   help="seed for the random kill steps")
    p.add_argument("--fast", action="store_true",
                   help="CI mode: 1 kill over 6 steps")
    p.add_argument("--workdir", default="",
                   help="scratch dir (default: a fresh tempdir, removed on "
                        "success)")
    args = p.parse_args()
    if args.fast:
        args.kills, args.steps = 1, 6

    work = args.workdir or tempfile.mkdtemp(prefix="chaos_train_")
    os.makedirs(work, exist_ok=True)
    ref_dir = os.path.join(work, "reference")
    chaos_dir = os.path.join(work, "chaos")
    for d in (ref_dir, chaos_dir):
        shutil.rmtree(d, ignore_errors=True)

    # kill boundaries: strictly increasing (each run resumes PAST the
    # previous kill), strictly inside (0, steps) so every kill interrupts
    # real remaining work
    if args.kills >= args.steps:
        print("chaos_train: need --steps > --kills", file=sys.stderr)
        return 2
    kills = sorted(random.Random(args.seed).sample(
        range(1, args.steps), args.kills))
    print(f"chaos_train: {args.steps} steps, kills at {kills}, "
          f"workdir {work}")

    print("chaos_train: reference run (uninterrupted)")
    ref = run_task(ref_dir, args.steps)
    if ref.returncode != 0:
        print(ref.stdout + ref.stderr, file=sys.stderr)
        print("chaos_train: reference run failed", file=sys.stderr)
        return 1

    for n, kill in enumerate(kills):
        out = run_task(chaos_dir, args.steps, kill_step=kill)
        text = out.stdout + out.stderr
        if out.returncode != EXIT_PREEMPTED:
            print(text, file=sys.stderr)
            print(f"chaos_train: kill #{n + 1} at step {kill}: expected "
                  f"exit {EXIT_PREEMPTED}, got {out.returncode}",
                  file=sys.stderr)
            return 1
        if f"emergency checkpoint step={kill}" not in text:
            print(text, file=sys.stderr)
            print(f"chaos_train: no 'emergency checkpoint step={kill}' "
                  "line", file=sys.stderr)
            return 1
        if n > 0 and "Resumed from checkpoint step" not in text:
            print(text, file=sys.stderr)
            print(f"chaos_train: kill #{n + 1} did not resume from a "
                  "checkpoint", file=sys.stderr)
            return 1
        print(f"chaos_train: kill #{n + 1}: SIGTERM at step {kill} → "
              f"emergency checkpoint + exit {EXIT_PREEMPTED}")

    final = run_task(chaos_dir, args.steps)
    text = final.stdout + final.stderr
    if final.returncode != 0:
        print(text, file=sys.stderr)
        print("chaos_train: final resume failed", file=sys.stderr)
        return 1
    if f"Resumed from checkpoint step {kills[-1]}" not in text:
        print(text, file=sys.stderr)
        print(f"chaos_train: final run did not resume from step "
              f"{kills[-1]}", file=sys.stderr)
        return 1
    print(f"chaos_train: final resume from step {kills[-1]} → "
          f"{args.steps} steps complete")

    diffs = trees_bitwise_equal(load_final(ref_dir, args.steps),
                                load_final(chaos_dir, args.steps))
    if diffs:
        print("chaos_train: FINAL STATE DIVERGED after kill/resume at "
              f"leaves: {diffs[:10]}", file=sys.stderr)
        return 1
    print(f"chaos_train: OK — {args.kills} kill/resume cycle(s), final "
          "params/opt-state/batch-stats bitwise-identical to the "
          "uninterrupted run")
    if not args.workdir:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
