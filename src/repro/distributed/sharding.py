"""Sharding rules: logical parameter/activation axes -> mesh PartitionSpecs.

Layout (single pod, mesh ("data", "model")):
  * batch            -> ("pod","data")  (pure DP across pods, see below)
  * FSDP             -> params' "embed"-like dims sharded over "data"
                        (ZeRO-3: optimizer state inherits the same specs)
  * TP               -> head/ffn/vocab dims over "model"
  * experts          -> replicated (TP inside experts); EP variant in §Perf

Multi-pod mesh ("pod","data","model") keeps parameters replicated across the
"pod" axis (gradient all-reduce over pod = classic cross-pod DP) and FSDP
within a pod — ICI-friendly: the heavy FSDP all-gathers stay inside a pod.

Conflict rule: logical axes are resolved left-to-right; a mesh axis may appear
only once per spec, later claims fall back to replication (flax-style).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import logical_axes, is_pspec

# logical axis -> mesh axis (or None)
PARAM_RULES = {
    "vocab": "model",
    "embed": "data",          # FSDP
    "heads": "model",
    "kv": "model",
    "ffn": "model",
    "experts": None,
    "experts_dim": None,
    "lru": "model",
    "lru_out": "data",
    "gates": "model",
    "conv": None,
    "layers": None,
    "sheads": None,
    "shead_dim": None,
}

ACT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed_act": None,
    "vocab_act": "model",
    "experts_act": None,
    "ffn_act": "model",
    "heads_act": "model",     # Megatron-style attention head sharding (§Perf)
    "kv_act": None,           # kv heads replicated across TP for attention
    "head_dim": None,
}


def _axis_size(mesh, m):
    if isinstance(m, tuple):
        n = 1
        for a in m:
            n *= mesh.shape[a]
        return n
    return mesh.shape[m]


def _resolve(axes, rules, mesh, shape=None):
    """Resolve logical axes to a PartitionSpec.

    pjit argument shardings require exact divisibility (GSPMD pads only
    intermediates), so any mapping whose mesh-axis product does not divide the
    dimension is dropped to replication.
    """
    mesh_axes = set(mesh.axis_names)
    spec, used = [], set()
    for i, ax in enumerate(axes):
        m = rules.get(ax) if not isinstance(ax, (tuple, type(None))) else ax
        if isinstance(ax, tuple):  # already a concrete mesh-axis tuple
            m = ax
        if isinstance(m, tuple):
            m = tuple(a for a in m if a in mesh_axes and a not in used)
            m = m or None
        elif m is not None and (m in used or m not in mesh_axes):
            m = None
        if m is not None and shape is not None:
            if shape[i] % _axis_size(mesh, m) != 0:
                m = None
        if m is not None:
            used.update(m if isinstance(m, tuple) else [m])
        spec.append(m)
    return P(*spec)


def param_pspecs(template, mesh, rules=None):
    """PartitionSpec tree mirroring the parameter template (shape-checked)."""
    rules = rules or PARAM_RULES
    return jax.tree_util.tree_map(
        lambda p: _resolve(p.axes, rules, mesh, p.shape),
        template, is_leaf=is_pspec,
    )


def sanitize(pspec_tree, abstract_tree, mesh):
    """Drop non-divisible mesh axes from an existing PartitionSpec tree,
    checking each spec against the matching abstract leaf's shape."""

    def fix(spec, leaf):
        out, used = [], set()
        spec = tuple(spec) + (None,) * (len(leaf.shape) - len(spec))
        for i, m in enumerate(spec):
            if isinstance(m, tuple):
                m = tuple(a for a in m if a in mesh.shape and a not in used) or None
            elif m is not None and (m not in mesh.shape or m in used):
                m = None
            if m is not None and leaf.shape[i] % _axis_size(mesh, m) != 0:
                m = None
            if m is not None:
                used.update(m if isinstance(m, tuple) else [m])
            out.append(m)
        return P(*out)

    flat_specs = jax.tree_util.tree_leaves(
        pspec_tree, is_leaf=lambda x: isinstance(x, P))
    flat_leaves, treedef = jax.tree_util.tree_flatten(abstract_tree)
    assert len(flat_specs) == len(flat_leaves), (
        len(flat_specs), len(flat_leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [fix(s, l) for s, l in zip(flat_specs, flat_leaves)])


def named(tree_of_pspecs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_of_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def make_constrain(mesh, rules=None):
    """Activation-sharding hook passed into ``forward`` (no-op off-mesh).

    Unlike pjit *argument* shardings, intermediates may be GSPMD-padded, so a
    non-divisible mapping is kept when the padding waste is small (e.g. 40
    q-heads over 16 ranks -> pad to 48, 20% waste) and dropped otherwise
    (e.g. batch=1 over 16 ranks)."""
    rules = rules or ACT_RULES

    def cons(x, axes):
        axes = tuple(axes[: x.ndim]) + (None,) * (x.ndim - len(axes))
        spec0 = _resolve(axes, rules, mesh, shape=None)
        fixed, used = [], set()
        for i, m in enumerate(spec0):
            if m is not None:
                n = _axis_size(mesh, m)
                d = x.shape[i]
                pad = (-(-d // n) * n - d) / max(d, 1)
                if d % n != 0 and pad > 0.34:
                    m = None
            if m is not None:
                used.update(m if isinstance(m, tuple) else [m])
            fixed.append(m)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*fixed)))

    return cons


# ------------------------------------------------------ cache / batch ----


def batch_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def block_cache_pspec(cfg, kind, mesh, kv_shard="kv_heads"):
    """PartitionSpec tree matching init_block_cache's structure.

    kv_shard: 'kv_heads' (baseline: shard cache heads over model, GSPMD pads
    non-divisible head counts) or 'seq' (sequence-parallel KV cache — §Perf).
    """
    b = P(*batch_axes(mesh)) if batch_axes(mesh) else P()
    ba = batch_axes(mesh)
    if kv_shard == "seq":
        kv = lambda: {"k": P(ba, "model", None, None),
                      "v": P(ba, "model", None, None),
                      "pos": P(ba, "model")}
    else:
        kv = lambda: {"k": P(ba, None, "model", None),
                      "v": P(ba, None, "model", None),
                      "pos": P(ba, None)}
    if kind in ("attn", "moe"):
        return kv()
    if kind == "xattn":
        c = kv()
        c["ck"] = P(ba, None, "model", None)
        c["cv"] = P(ba, None, "model", None)
        return c
    if kind == "mlstm":
        return {"C": P(ba, "model", None, None), "n": P(ba, "model", None),
                "m": P(ba, "model")}
    if kind == "slstm":
        return {k: P(ba, "model") for k in ("c", "n", "h", "m")}
    if kind == "rglru":
        return {"h": P(ba, "model"), "conv": P(ba, None, "model")}
    raise ValueError(kind)


def cache_pspecs(cfg, mesh, kv_shard="kv_heads"):
    group, n_full, rem = cfg.layer_groups()
    add_layer = lambda spec: P(None, *spec)
    gc = tuple(
        jax.tree_util.tree_map(
            add_layer, block_cache_pspec(cfg, k, mesh, kv_shard),
            is_leaf=lambda x: isinstance(x, P),
        )
        for k in group
    )
    tail = tuple(block_cache_pspec(cfg, k, mesh, kv_shard) for k in rem)
    return {"groups": gc, "tail": tail}


def input_pspecs(cfg, shape_kind, mesh):
    ba = batch_axes(mesh)
    d = {"tokens": P(ba, None)}
    if shape_kind == "train":
        d["targets"] = P(ba, None)
    if shape_kind == "decode":
        d["positions"] = P(ba)
    if cfg.is_encoder_decoder or cfg.n_img_tokens:
        if shape_kind != "decode":
            d["cross_src"] = P(ba, None, None)
    return d
