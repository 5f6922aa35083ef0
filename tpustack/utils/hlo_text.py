"""Reading a compiled serving program's optimised HLO text: what it
writes, by opcode, inside and outside the decode scan, under tiled layouts
— and compiling the engine's programs for a DESCRIBED (not attached) TPU
v5e to get that text, on ``ShapeDtypeStruct``s: no weight is made, no chip
is needed.  ``tools/hlo_where.py`` prints it;
``tests/test_pallas_lowering.py`` holds the pool's layout to it.

Nothing runs, so this says nothing about times: those come from a chip
trace.  Only one process may hold libtpu while a program compiles.
"""

import collections
import dataclasses
import os
import re
import unittest.mock

#: opcodes that write nothing of their own (views, plumbing, and the
#: ``-start`` half of an async pair: its ``-done`` carries the result)
FREE = frozenset({
    "parameter", "tuple", "get-tuple-element", "bitcast", "constant", "while",
    "conditional", "call", "after-all", "partition-id", "replica-id",
    "slice-start", "copy-start", "dynamic-slice-start",
    "dynamic-update-slice-start", "async-start", "async-update", "iota"})

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}
_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{([^}]*)\})?")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([a-z][a-z\-]*)\(")
_COMP = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|branch_computations|"
    r"true_computation|false_computation|called_computations)="
    r"\{?([%\w.\-, ]+)\}?")


@dataclasses.dataclass(frozen=True)
class Instr:
    name: str
    opcode: str
    shapes: tuple      # ((dtype, dims, layout text), ...) of the result
    comp: str
    line: str

    @property
    def in_place(self) -> bool:
        """The result aliases an operand (a scatter or an update into a
        donated buffer): only the updates are written."""
        return bool(re.search(r'"aliasing_operands":\{"lists":\[\{', self.line))

    @property
    def nbytes(self) -> int:
        return 0 if self.in_place else sum(padded_bytes(*s)
                                           for s in self.shapes)


def padded_bytes(dtype: str, dims: tuple, layout: str = "") -> int:
    """Bytes of one array under its layout's first tile: each tiled dim
    (the minor-most ones, in ``minor_to_major`` order) rounds up to the
    tile.  No layout, or none with a tile: the plain size."""
    size = _ITEMSIZE.get(dtype)
    if size is None:           # token, opaque: nothing to count
        return 0
    dims = list(dims)
    order, _, rest = layout.partition(":")
    tile = re.search(r"T\(([0-9,]+)\)", rest)
    if tile and dims:
        m2m = ([int(x) for x in order.split(",") if x != ""]
               or list(range(len(dims) - 1, -1, -1)))
        t = [int(x) for x in tile.group(1).split(",")]
        # tile dims are major-to-minor over the minor-most len(t) dims
        for td, axis in zip(reversed(t), m2m):
            dims[axis] = -(-dims[axis] // td) * td
    n = 1
    for d in dims:
        n *= d
    return n * size


def parse(text: str):
    """``(instrs, calls, fused, bodies)``: every instruction with its
    computation, the call graph ``{computation: {callee, ...}}``, the
    fusion computations, and the ``while`` bodies and conditions."""
    instrs, calls, fused, bodies = [], collections.defaultdict(set), set(), set()
    comp = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(2)
            continue
        if comp is None or line.strip() == "}":
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, opcode = m.groups()
        shapes = tuple((d, tuple(int(x) for x in dims.split(",") if x), lay or "")
                       for d, dims, lay in _SHAPE.findall(result))
        instrs.append(Instr(name, opcode, shapes, comp, line.strip()))
        for group in _CALLED.findall(line):
            for callee in group.split(","):
                callee = callee.strip().lstrip("%")
                if callee:
                    calls[comp].add(callee)
                    if opcode == "fusion":
                        fused.add(callee)
        if opcode == "while":
            b = re.search(r"body=%?([\w.\-]+)", line)
            c = re.search(r"condition=%?([\w.\-]+)", line)
            bodies.update(x.group(1) for x in (b, c) if x)
    return instrs, calls, fused, bodies


def where(text: str):
    """Instructions outside every fusion, as ``(instr, in_scan)``: in_scan
    when a ``while`` body or condition holds it, directly or through a
    computation that one calls."""
    instrs, calls, fused, bodies = parse(text)
    in_scan, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in in_scan:
            in_scan.add(c)
            todo.extend(calls.get(c, ()))
    # reducers and other applied computations inside fusions are fused too
    hidden, todo = set(), list(fused)
    while todo:
        c = todo.pop()
        if c not in hidden:
            hidden.add(c)
            todo.extend(calls.get(c, ()))
    return [(i, i.comp in in_scan) for i in instrs
            if i.comp not in hidden and i.opcode not in FREE]


#: what moves data and computes nothing, in the optimised text (the
#: ``-start`` halves are in ``FREE``: a pair is counted at its ``-done``)
MOVERS = frozenset({"copy", "reshape", "slice-done", "copy-done", "pad"})


def pool_relayouts(text: str, n_blocks: int, block: int, floor_bytes: int,
                   in_scan: bool = False):
    """Data-moving instructions outside fusions, and outside the scan (or,
    ``in_scan``, inside it), whose result is shaped like the pool (leading
    dimension ``n_blocks`` or ``n_blocks * block``) and takes at least
    ``floor_bytes`` under its tiled layout.  Outside the scan: a relayout
    or a padded copy of the pool, once a chunk.  Inside: the pool staged
    whole ahead of a kernel call, every step.  The weights' own copies and
    prefetch slices never have that leading dimension."""
    lead = {n_blocks, n_blocks * block}
    return [i for i, scan in where(text)
            if scan is in_scan and i.opcode in MOVERS
            and any(dims and dims[0] in lead and
                    padded_bytes(dt, dims, lay) >= floor_bytes
                    for dt, dims, lay in i.shapes)]


# ------------------------------------------------------------ the programs
def describe_v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def serving_program(program: str, cfg, chip, *, rows: int, pool_blocks: int,
                    block: int = 64, steps: int = 16, bucket: int = 512,
                    dtype=None):
    """``(jitted, args, kwargs)`` of a paged serving program of ``cfg`` on
    shapes placed on ``chip``: ``decode`` (``rows`` slots, a capacity of
    ``steps`` steps, ``flash=True``) or ``admit`` (``rows`` rows of ``bucket``).
    The engine's own jitted methods, so the pool is donated as it is when
    served."""
    import jax
    import jax.numpy as jnp

    from tpustack.models.llama import LlamaModel, init_kv_pool
    from tpustack.models.llm_generate import Generator

    dtype = dtype or jnp.bfloat16
    on_chip = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=chip), t)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    params = on_chip(jax.eval_shape(
        lambda: LlamaModel(cfg, dtype=dtype).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    gen = Generator(cfg, params=params, dtype=dtype)
    pool = on_chip(jax.eval_shape(
        lambda: init_kv_pool(cfg, pool_blocks, block, dtype=dtype)))
    nb = cfg.max_seq // block
    i32, f32 = jnp.int32, jnp.float32
    B = rows
    slot = lambda dt: sds((B,), dt)
    if program == "decode":
        fn, kwargs = Generator._decode_scan_paged, {"flash": True}
        args = (gen, params, sds((B, 1), i32), slot(i32), slot(i32), pool,
                sds((B, nb), i32), sds((B, 2), jnp.uint32), slot(f32),
                slot(i32), slot(jnp.bool_), steps, sds((), i32))
    elif program == "admit":
        n, slots = rows, max(rows, 8)
        row = lambda dt: sds((n,), dt)
        st = lambda dt: sds((slots,), dt)
        fn, kwargs = Generator._admit_fused_paged, {}
        args = (gen, params, sds((n, bucket), i32), pool, sds((n, nb), i32),
                row(i32), row(i32), row(i32), row(i32),
                st(i32), st(i32), sds((slots, 1), i32), st(f32), st(i32),
                st(jnp.bool_), sds((slots, 2), jnp.uint32), row(f32),
                row(i32), row(jnp.bool_))
    else:
        raise ValueError(f"program {program!r}: decode or admit")
    return fn, args, kwargs


def compile_program(fn, args, kwargs=None):
    """The jitted ``fn(*args, **kwargs)`` compiled for the described chip
    the args are placed on, traced as a TPU process would trace it
    (``auto`` picks the Pallas kernels, interpret off)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # an entry written for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    chip = next(iter(next(
        x.sharding for x in jax.tree.leaves(args)
        if getattr(x, "sharding", None) is not None).device_set))
    described = jax.sharding.AbstractMesh(
        (), (), abstract_device=jax.sharding.AbstractDevice(
            chip.device_kind, chip.num_cores))
    try:
        # the backend's name and the chip's kind are what a trace asks of
        # the device it is for (which kernels, how much VMEM)
        with unittest.mock.patch.object(jax, "default_backend",
                                        lambda: "tpu"), \
                jax.sharding.use_abstract_mesh(described):
            lowered = fn.lower(*args, **(kwargs or {}))
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


#: the configurations the benchmark serves, and the slots each runs with
#: (``LlamaConfig`` presets; the pool holds slots x ctx / block + 1 blocks)
SERVED_SLOTS = {"qwen25_7b": 8, "k_exaone_236b_ep8": 16}


def serving_config(preset: str, layers: int, kv: str = "int8"):
    """A preset as the benchmark serves it (ctx 4096, int8 weights, an
    int8 or a float pool), cut to its first ``layers`` layers (0: all)."""
    from tpustack.models.llama import LlamaConfig

    cfg = getattr(LlamaConfig, preset)()
    layers = min(layers, cfg.n_layers) if layers else cfg.n_layers
    return dataclasses.replace(
        cfg, n_layers=layers, max_seq=4096, quant="int8",
        kv_quant="int8" if kv == "int8" else None,
        layers=cfg.layers[:layers] if cfg.layers else None)
