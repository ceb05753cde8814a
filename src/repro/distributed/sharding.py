"""Logical→mesh sharding rules (MaxText-style, resolved dynamically).

Mesh axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
"pod" composes with "data" for everything batch/FSDP-sharded, so the same
rules serve both meshes.  On a 1-device test mesh all rules collapse to
replication automatically (PartitionSpec axes not in the mesh are invalid,
hence the dynamic resolution here).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def current_mesh():
    """The mesh installed by ``jax.set_mesh`` (as an AbstractMesh), or None
    outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def unchecked_shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the BBMM
    bodies mix replicated and per-device values by design)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def ordered_psum(x, axes=("data",)):
    """Deterministic-order cross-device sum: all-gather the per-device
    partials, then fold them left-to-right in device-index order from a
    zeros accumulator.

    ``jax.lax.psum`` leaves the floating-point reduction order up to the
    backend (ring vs tree, implementation-defined), so a sharded sum is
    generally NOT bitwise-equal to the same sum on one device.  This fold
    is: it reproduces exactly the left fold a single device performs when
    it scans the same partials in the same order — the contract the
    panel-fused CG step relies on for its bitwise 1-vs-N-device guarantee.
    O(S·|x|) gather instead of psum's O(|x|), fine for the (4, t)-sized
    reduction slabs it exists for; don't use it for large operands."""
    parts = jax.lax.all_gather(x, axes, axis=0, tiled=False)
    total = jnp.zeros_like(parts[0])
    for k in range(parts.shape[0]):
        total = total + parts[k]
    return total


def row_shard_spec(ndim, axes=("data",)):
    """P(…, axes, None): shard the row (-2) dim of an (…, n, t) operand over
    ``axes``, leading batch dims replicated — the layout of M and of the
    matmul output in every row-partitioned BBMM path (2-dim RHS and the
    native-batch 3-dim RHS alike)."""
    return P(*([None] * (ndim - 2)), axes, None)


def mesh_axes():
    mesh = current_mesh()
    return tuple(mesh.axis_names) if mesh is not None else ()


def batch_axes():
    """The data-parallel axes present in the current mesh."""
    ax = mesh_axes()
    return tuple(a for a in ("pod", "data") if a in ax)


def has_model_axis():
    return "model" in mesh_axes()


def mesh_axis_sizes(mesh):
    """{axis_name: size} of a Mesh or AbstractMesh."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def axis_size(name):
    mesh = current_mesh()
    if mesh is None:
        return 1
    return mesh_axis_sizes(mesh).get(name, 1)


def p_batch(*rest):
    """P(batch..., *rest) resolved for the live mesh."""
    ba = batch_axes()
    return P(ba if ba else None, *rest)


def shard_activations(x, *rest):
    """Constrain (B, ...) activations: batch over data axes; any named rest
    axes are sanitized against the live mesh (and divisibility)."""
    if not batch_axes():
        return x
    live = set(mesh_axes())
    clean = []
    for dim, a in zip(x.shape[1:], rest):
        if a is None or a not in live or dim % axis_size(a) != 0:
            clean.append(None)
        else:
            clean.append(a)
    return jax.lax.with_sharding_constraint(x, p_batch(*clean))


def shard_cache_kv(cache_kv):
    """KV cache (B, S, KV, hd): batch over data; kv-heads over model when
    divisible, else head_dim over model, else replicated."""
    if not mesh_axes():
        return cache_kv
    m = axis_size("model")
    B, S, KV, hd = cache_kv.shape
    if m > 1 and KV % m == 0:
        spec = p_batch(None, "model", None)
    elif m > 1 and hd % m == 0:
        spec = p_batch(None, None, "model")
    else:
        spec = p_batch(None, None, None)
    return jax.lax.with_sharding_constraint(cache_kv, spec)


# -- parameter rules -----------------------------------------------------------
# matched against the '/'-joined pytree path; first hit wins. Axes are
# logical: "model" = TP, "data" = FSDP (params gathered on use by XLA).

_RULES = [
    # embeddings / unembedding
    (r"embed/table$", ("model", "data")),  # (V, D)
    (r"lm_head$", ("data", "model")),  # (D, V)
    (r"pos_table$", (None, "data")),
    # attention (GQA)
    (r"(wq|wk|wv)$", ("data", "model")),
    (r"wo$", ("model", "data")),
    (r"(bq|bk|bv)$", ("model",)),
    # MLA
    (r"w_dkv$", ("data", None)),
    (r"w_kr$", ("data", None)),
    (r"w_dq$", ("data", None)),
    (r"(w_uk|w_uv|w_uq)$", (None, "model")),
    (r"(kv_norm|q_norm)$", (None,)),
    # MoE (leading expert dim) — must precede the generic MLP rules
    (r"experts/(w_gate|w_in)$", ("model", "data", None)),
    (r"experts/w_out$", ("model", None, "data")),
    (r"router$", ("data", None)),
    # MLPs
    (r"(w_gate|w_in)$", ("data", "model")),
    (r"w_out$", ("model", "data")),
    (r"(b_in)$", ("model",)),
    (r"(b_out)$", (None,)),
    # Mamba2
    (r"in_proj$", ("data", "model")),
    (r"out_proj$", ("model", "data")),
    (r"conv_w$", (None, "model")),
    (r"conv_b$", ("model",)),
    (r"(A_log|dt_bias|D)$", (None,)),
    (r"out_norm$", ("model",)),
    # norms & leftovers
    (r"(scale|bias)$", (None,)),
]


def param_spec(path: str, ndim: int, stacked_dims: int = 0) -> P:
    """PartitionSpec for a parameter at '/'-joined ``path``.

    stacked_dims: number of leading scan-stacking dims (layers) to leave
    unsharded before the rule applies.
    """
    live = set(mesh_axes())
    for pat, axes in _RULES:
        if re.search(pat, path):
            body_ndim = ndim - stacked_dims
            axes = axes[:body_ndim]
            resolved = []
            for a in axes:
                if a is None or a not in live:
                    resolved.append(None)
                else:
                    resolved.append(a)
            resolved += [None] * (body_ndim - len(resolved))
            return P(*([None] * stacked_dims), *resolved)
    return P(*([None] * ndim))


def _path_str(path):
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def params_shardings(params, stacked_paths=()):
    """Pytree of PartitionSpecs matching ``params``.

    stacked_paths: mapping (or iterable of pairs) regex → number of leading
    layer-stacking dims the matching subtree's leaves carry (scan stacking).
    """
    stacked_paths = dict(stacked_paths)

    def spec(path, leaf):
        ps = _path_str(path)
        stacked = 0
        for pat, n in stacked_paths.items():
            if re.search(pat, ps):
                stacked = n
                break
        return param_spec(ps, leaf.ndim if hasattr(leaf, "ndim") else 0, stacked)

    return jax.tree_util.tree_map_with_path(spec, params)


def shard_q_like_cache(q, num_kv_heads):
    """Constrain decode-time q (B, S, H, hd) to the same model-axis layout
    as the KV cache (kv-heads over "model" when divisible, else head_dim).
    Misaligned q makes the SPMD partitioner all-gather the *cache* at every
    layer's attention einsum — GBs per decoded token."""
    if not mesh_axes():
        return q
    m = axis_size("model")
    B, S, H, hd = q.shape
    if m > 1 and num_kv_heads % m == 0 and H % m == 0:
        spec = p_batch(None, "model", None)
    elif m > 1 and hd % m == 0:
        spec = p_batch(None, None, "model")
    else:
        return q
    return jax.lax.with_sharding_constraint(q, spec)


_CACHE_LAYOUTS = {
    # trailing-dim layouts by leaf name
    "k": ("B", "T", "KV", "hd"),
    "v": ("B", "T", "KV", "hd"),
    "self_k": ("B", "T", "KV", "hd"),
    "self_v": ("B", "T", "KV", "hd"),
    "cross_k": ("B", "T", "KV", "hd"),
    "cross_v": ("B", "T", "KV", "hd"),
    "attn_k": ("B", "T", "KV", "hd"),
    "attn_v": ("B", "T", "KV", "hd"),
    "c_kv": ("B", "T", "r"),
    "k_rope": ("B", "T", "r"),
    "conv": ("B", "w", "ch"),
    "ssd": ("B", "H", "dh", "ds"),
}


def cache_shardings(cache_shapes):
    """PartitionSpec tree for a decode cache (ShapeDtypeStruct tree).

    Batch shards over the data axes when divisible; for batch-1 long-context
    cells the *sequence* dim of KV caches shards over "data" instead (SP).
    KV-heads (or channels) shard over "model" when divisible, else head_dim.
    """
    live = set(mesh_axes())
    m = axis_size("model")
    dsz = 1
    for a in batch_axes():
        dsz *= axis_size(a)

    def spec(path, leaf):
        name = _path_str(path).split("/")[-1]
        layout = _CACHE_LAYOUTS.get(name)
        if layout is None or not live:
            return P(*([None] * leaf.ndim))
        lead = leaf.ndim - len(layout)
        dims = list(leaf.shape[lead:])
        out = [None] * len(layout)
        b = dims[layout.index("B")]
        batch_sharded = b % dsz == 0 and dsz > 1
        if batch_sharded:
            out[layout.index("B")] = batch_axes()
        for i, (ax, size) in enumerate(zip(layout, dims)):
            if ax == "T" and not batch_sharded and "data" in live and size % axis_size("data") == 0:
                out[i] = "data"
            if ax in ("KV", "H", "ch") and m > 1 and size % m == 0 and "model" in live:
                out[i] = "model"
            if ax == "hd" and out[layout.index("KV")] is None and m > 1 and size % m == 0:
                out[i] = "model"
        return P(*([None] * lead), *out)

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)


def named_shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
