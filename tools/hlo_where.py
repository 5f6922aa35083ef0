#!/usr/bin/env python3
"""Where a compiled serving program writes its bytes: by opcode, inside
and outside the decode scan, for a DESCRIBED (not attached) TPU v5e.

    python tools/hlo_where.py decode --layers 2
    python tools/hlo_where.py admit --preset k_exaone_236b_ep8 --layers 2
    python tools/hlo_where.py decode --layers 0 --dump /root/scratch/d.txt
    python tools/hlo_where.py admit --bucket 4096 --rows 4 --layers 2

Compiles ``Generator._decode_scan_paged`` (``decode``: a capacity of 16
steps, the steps run an operand, ``flash=True``, every slot live) or
``_admit_fused_paged`` (``admit``: ``--rows`` rows, default one, of the
``--bucket`` bucket, default 512; a bucket above ``Generator.ADMIT_CHUNK``
walks its chunks in a ``while``, and what that body holds counts as inside
the scan) of a served configuration on ``ShapeDtypeStruct``s
(``tpustack/utils/hlo_text.py``) and reads the optimised HLO: every
instruction that is not inside a fusion, with the bytes its result takes
UNDER ITS TILED LAYOUT (an ``f32[512,64,4]{2,1,0:T(8,128)}`` is 16.8 MB,
not 0.5), split by whether a ``while`` body holds it.  What runs outside
the scan runs once a chunk; a pool-sized ``copy``/``reshape``/``slice-done``
there is a relayout of the pool (PR 29 found 3.2 GB of them a chunk this
way), and one inside the scan is the pool staged whole ahead of a kernel
call, every step.  Nothing runs, so this says nothing about times.
"""

import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOP = 8     # largest single instructions listed per side


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from tpustack.utils.hlo_text import (SERVED_SLOTS, compile_program,
                                         describe_v5e, pool_relayouts,
                                         serving_config, serving_program,
                                         where)

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("program", choices=["decode", "admit"])
    ap.add_argument("--preset", default="qwen25_7b",
                    choices=sorted(SERVED_SLOTS))
    ap.add_argument("--layers", type=int, default=2,
                    help="layers compiled (0: all the preset has)")
    ap.add_argument("--bucket", type=int, default=512,
                    help="admit: the prompt bucket compiled")
    ap.add_argument("--rows", type=int, default=1,
                    help="admit: rows admitted together")
    ap.add_argument("--dump", help="write the optimised HLO text here")
    a = ap.parse_args(argv)

    cfg = serving_config(a.preset, a.layers)
    slots, block = SERVED_SLOTS[a.preset], 64
    rows = slots if a.program == "decode" else a.rows
    n_blocks = slots * (cfg.max_seq // block) + 1
    compiled = compile_program(*serving_program(
        a.program, cfg, describe_v5e(), rows=rows, pool_blocks=n_blocks,
        block=block, bucket=a.bucket))
    text = compiled.as_text()
    if a.dump:
        with open(a.dump, "w") as f:
            f.write(text)
    print(f"{a.program} of {a.preset}: {cfg.n_layers} layers, {rows} rows"
          + (f" of bucket {a.bucket}, " if a.program == "admit" else ", ") +
          f"pool {n_blocks} x {block} tokens, int8 KV; compiled for a "
          "described v5e (bytes are results under their tiled layouts)")
    mem = compiled.memory_analysis()
    print(f"arguments {mem.argument_size_in_bytes / 1e6:,.1f} MB (of them "
          f"{mem.alias_size_in_bytes / 1e6:,.1f} donated into results), "
          f"results {mem.output_size_in_bytes / 1e6:,.1f} MB, temporaries "
          f"{mem.temp_size_in_bytes / 1e6:,.1f} MB")
    tensor = n_blocks * block * cfg.n_kv_heads * cfg.head_dim
    for side, want in (("outside the scan", False), ("inside the scan", True)):
        rows_ = [i for i, scan in where(text) if scan is want]
        by = collections.Counter()
        n = collections.Counter()
        for i in rows_:
            by[i.opcode] += i.nbytes
            n[i.opcode] += 1
        total = sum(by.values())
        print(f"\n{side}: {total / 1e6:,.1f} MB written by "
              f"{len(rows_)} instructions ("
              f"{sum(i.in_place for i in rows_)} of them update an operand "
              "in place and count 0)")
        moved = pool_relayouts(text, n_blocks, block, tensor // 4, want)
        print(f"  shaped like the pool, a quarter of a K/V tensor or more: "
              f"{len(moved)} x {sum(i.nbytes for i in moved) / 1e6:,.1f} MB")
        for op, b in by.most_common():
            if b < 0.001 * max(total, 1):
                continue
            print(f"  {op:<28}{n[op]:>6} x {b / 1e6:>12,.2f} MB")
        for i in sorted(rows_, key=lambda i: -i.nbytes)[:TOP]:
            shape = " ".join(f"{d}[{','.join(map(str, dims))}]{{{lay}}}"
                             for d, dims, lay in i.shapes)
            src = re.search(r'op_name="([^"]*)"', i.line)
            print(f"    {i.nbytes / 1e6:>9,.2f} MB  {i.opcode:<14}{shape}"
                  f"  {src.group(1)[-70:] if src else ''}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
