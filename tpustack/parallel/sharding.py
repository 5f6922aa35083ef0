"""Parameter/activation sharding rules (GSPMD via PartitionSpec).

The reference has no tensor-level parallelism at all — its scale story is k8s
Jobs with one GPU each and NCCL is never configured (SURVEY.md §2.10, §5.8).
The TPU build replaces that with the standard JAX recipe: pick a mesh
(``tpustack.parallel.mesh``), annotate params/activations with
``PartitionSpec``s, and let XLA insert the collectives over ICI/DCN.

Rules are (regex, spec) pairs matched against ``/``-joined param paths —
first match wins, scalars stay replicated.  The Llama rules are megatron-style
TP with FSDP on the complementary axis:

    column-parallel (q/k/v, gate/up, lm_head): kernel [in, out] → (fsdp, tp)
    row-parallel (o_proj, down_proj):          kernel [in, out] → (tp, fsdp)
    embeddings: vocab on tp, model dim on fsdp; norms replicated
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from tpustack.utils.tree import flat_paths as tree_paths

Rules = Sequence[Tuple[str, PS]]

LLAMA_RULES: Rules = (
    (r"embed_tokens/embedding$", PS("tp", "fsdp")),
    (r"(q_proj|k_proj|v_proj)/kernel$", PS("fsdp", "tp")),
    (r"(q_proj|k_proj|v_proj)/bias$", PS("tp")),
    (r"o_proj/kernel$", PS("tp", "fsdp")),
    (r"(gate_proj|up_proj)/kernel$", PS("fsdp", "tp")),
    (r"down_proj/kernel$", PS("tp", "fsdp")),
    (r"lm_head/kernel$", PS("fsdp", "tp")),
    (r"(layernorm|norm)[^/]*/scale$", PS()),
    (r".*", PS()),
)

# Pipelined Llama (models.llama_pipeline): layer params are stacked [L, ...]
# and cut over the pp axis (contiguous stage blocks); everything outside the
# trunk (embed/norm/lm_head) is small and replicated — tp/sp are 1 inside a
# pipeline stage (shard_map is manual mode, see parallel/pipeline.py).
LLAMA_PP_RULES: Rules = (
    (r"^layers/", PS("pp")),
    (r".*", PS()),
)

# SD1.5 UNet/VAE/CLIP: conv-heavy; at serving batch sizes the win is DP over
# images + replicated params (a 1GB bf16 UNet fits any chip), with TP on the
# big transformer Dense layers when a mesh is used.
SD15_RULES: Rules = (
    (r"(to_q|to_k|to_v|q_proj|k_proj|v_proj|fc1|proj_in)/kernel$", PS(None, "tp")),
    (r"(to_out|out_proj|fc2|proj_out)/kernel$", PS("tp", None)),
    (r".*", PS()),
)


def match_partition_rules(rules: Rules, params: Dict[str, Any]):
    """Pytree of PartitionSpec matching ``params``' structure.

    Pattern follows public JAX LLM codebases (see SNIPPETS.md [1]): regex over
    the joined path; 0-d/1-element leaves are always replicated.
    """

    def spec_for(path: str, leaf) -> PS:
        if getattr(leaf, "ndim", 0) == 0 or getattr(leaf, "size", 2) == 1:
            return PS()
        for pat, spec in rules:
            if re.search(pat, path):
                return _clip_spec(spec, leaf.ndim)
        raise ValueError(f"no partition rule for {path}")

    flat = tree_paths(params)
    specs = {path: spec_for(path, leaf) for path, leaf in flat}

    def rebuild(node, prefix):
        return {
            k: (rebuild(v, f"{prefix}/{k}" if prefix else k) if isinstance(v, dict)
                else specs[f"{prefix}/{k}" if prefix else k])
            for k, v in node.items()
        }

    return rebuild(params, "")


def _clip_spec(spec: PS, ndim: int) -> PS:
    """Trim a spec to the leaf's rank (rules written for 2-d kernels also hit
    biases etc.)."""
    parts = tuple(spec)
    if len(parts) <= ndim:
        return spec
    return PS(*parts[:ndim])


def shard_params(params, specs, mesh: Mesh):
    """device_put every leaf with its NamedSharding (host → sharded HBM)."""
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)


def constrain(x, mesh: Mesh, spec: PS):
    """with_sharding_constraint that is a no-op outside jit/mesh contexts."""
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except ValueError:
        return x


BATCH_SPEC = PS(("dp", "fsdp"), "sp")  # tokens [B, S]: batch over dp+fsdp, seq over sp


# --------------------------------------------------------------- serving KV
#
# Serving-time KV tensors shard on the HEAD axis over ``tp`` (Pope et al.
# 2022: attention is embarrassingly parallel per head, so each chip holds
# only its heads' K/V and the decode step's cache read/write never crosses
# ICI).  Every DENSE serving KV layout puts kv_heads at axis 2:
#
#     dense slot caches  [B, max_seq, kvh, hd]
#     chunk-local bufs   [B, chunk, kvh, hd]
#     int8 scale arrays  [..., kvh]           (axis 2 is the LAST axis)
#
# and the PAGED POOL rests in the layout its kernel and its scatter take
# (``llama.init_kv_pool``), heads folded into lanes and scales token-minor:
#
#     pool K/V           [n_blocks, block, kvh * hd]   (axis 2)
#     pool int8 scales   [n_blocks, kvh * block]       (axis 1)
#
# a folded axis shards in whole heads (kvh % tp == 0: each chip's slice is
# its heads' lanes, contiguous).
#
# When ``n_kv_heads`` does not divide the tp ways (GQA at high tp — e.g.
# 4 kv heads over tp=8), the K/V heads replicate per chip, matching what
# megatron-style sharding does to the kv projections in that regime; the
# partitioned programs stay correct either way, this only decides whether
# the cache HBM bill divides by tp.

def kv_head_axis_spec(ndim: int, pool_scale: bool = False) -> PS:
    """PartitionSpec sharding the kv-head axis on ``tp``: axis 2 of every
    dense layout (rank-3 scale arrays have it last) and of the pool's
    folded K/V; axis 1 of a POOL's scale plane (``pool_scale``)."""
    if pool_scale:
        return PS(None, "tp")
    return PS(*([None, None, "tp"] + [None] * (ndim - 3)))


def can_shard_kv_heads(mesh: Optional[Mesh], n_kv_heads: int) -> bool:
    """Head-axis KV sharding is available: a real tp axis whose ways
    divide the kv head count."""
    if mesh is None or "tp" not in mesh.axis_names:
        return False
    tp = int(mesh.shape["tp"])
    return tp > 1 and n_kv_heads % tp == 0


def shard_kv_tree(caches, mesh: Mesh, n_kv_heads: int, pool: bool = False):
    """device_put every serving-KV leaf (per-layer dicts of k/v [+ scales])
    with the head-axis NamedSharding; replicated when the heads don't
    divide tp.  ``pool``: the tree is a paged pool (``init_kv_pool``'s
    layout), whose ``*_scale`` planes fold the heads into axis 1.
    Idempotent on already-sharded trees."""
    shard = can_shard_kv_heads(mesh, n_kv_heads)

    def put(key, x):
        spec = (kv_head_axis_spec(x.ndim, pool and key.endswith("_scale"))
                if shard else PS())
        return jax.device_put(x, NamedSharding(mesh, spec))

    return [{k: put(k, x) for k, x in layer.items()} for layer in caches]


def tree_bytes(tree) -> int:
    """Total bytes across a pytree of arrays (global, all shards)."""
    import numpy as np

    return int(sum(np.prod(l.shape) * jax.numpy.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree)))


def tree_per_shard_bytes(tree) -> int:
    """Per-device bytes of a pytree of (possibly sharded) arrays — the
    honest per-chip HBM bill: each leaf counts its largest single-device
    shard (``NamedSharding.shard_shape``); unsharded/host leaves count
    whole.  This is what ``/props`` and the admission math report."""
    import numpy as np

    total = 0
    for l in jax.tree.leaves(tree):
        itemsize = jax.numpy.dtype(l.dtype).itemsize
        sharding = getattr(l, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = sharding.shard_shape(l.shape)
        else:
            shape = l.shape
        total += int(np.prod(shape)) * itemsize
    return total


def mesh_axis_sizes(mesh: Optional[Mesh]) -> Dict[str, int]:
    """{axis: ways} of a mesh ({} when None) — the /props + gauge shape."""
    if mesh is None:
        return {}
    return {str(a): int(mesh.shape[a]) for a in mesh.axis_names}


def export_mesh_axis_gauges(metrics, server: str, mesh: Optional[Mesh]) -> None:
    """Set ``tpustack_mesh_axis_chips{server,axis}`` for every mesh axis
    (the unsharded fallback exports dp=tp=1 so dashboards always have the
    series) — ONE exporter shared by the serving processes, so the gauge
    shape cannot drift between them."""
    for axis, ways in (mesh_axis_sizes(mesh) or {"dp": 1, "tp": 1}).items():
        metrics["tpustack_mesh_axis_chips"].labels(server=server,
                                                   axis=axis).set(ways)
