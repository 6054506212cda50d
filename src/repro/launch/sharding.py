"""Logical-axis sharding: the single place where model code meets the mesh.

Model code annotates tensors with LOGICAL axis names (``"batch"``,
``"embed"``, ``"heads"``, ``"expert"``, ...).  A :class:`ShardingRules`
context maps logical names to mesh axes; outside a context every annotation
is a no-op, so the same model code runs on 1 CPU device (smoke tests) and on
the 512-chip production mesh (dry-run) unchanged.

This is the MaxText/Flaxformer "logical axis rules" pattern, reduced to a
contextvar + two functions.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical name -> mesh axis (or tuple of axes, or None=replicate)
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),     # global batch (DP; pod axis exists multi-pod)
    "fsdp": "data",               # ZeRO-3 weight sharding axis
    "model": "model",             # TP axis (heads / ffn / vocab / experts)
    "seq": None,                  # sequence: replicated by default (SP opt-in)
    "expert": "model",            # EP shares the TP axis
    None: None,
}

_ACTIVE: contextvars.ContextVar[Optional["ShardingRules"]] = \
    contextvars.ContextVar("sharding_rules", default=None)


class ShardingRules:
    """Mesh + logical->physical mapping, entered as a context manager."""

    def __init__(self, mesh: Mesh, rules: dict | None = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)
        # drop mappings to mesh axes that don't exist (e.g. "pod" single-pod)
        names = set(mesh.axis_names)

        def fix(v):
            if v is None:
                return None
            if isinstance(v, tuple):
                kept = tuple(a for a in v if a in names)
                return kept if kept else None
            return v if v in names else None

        self.rules = {k: fix(v) for k, v in self.rules.items()}
        self._token = None

    def spec(self, *logical) -> P:
        return P(*(self.rules.get(ax, None) for ax in logical))

    def sharding(self, *logical) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))

    def __enter__(self):
        self._token = _ACTIVE.set(self)
        return self

    def __exit__(self, *exc):
        _ACTIVE.reset(self._token)
        return False


def active_rules() -> Optional[ShardingRules]:
    return _ACTIVE.get()


def manual_shard_map(f, mesh, manual_axes, in_specs, out_specs, *,
                     auto_rest: bool = True):
    """``jax.shard_map`` MANUAL over ``manual_axes``.

    With ``auto_rest`` the remaining mesh axes stay under GSPMD
    (partial-manual); without it the region is fully manual and the
    unmentioned axes compute redundantly.
    """
    kw = {"axis_names": frozenset(manual_axes)} if auto_rest else {}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def shard(x, *logical):
    """Annotate ``x`` with logical axes; no-op without active rules.

    Inside a partial-manual shard_map (compressed-grad path) the manual
    axes are stripped from the constraint: the body sees per-shard values,
    so constraining them on the manual axis would make GSPMD insert bogus
    cross-axis reshards.  Manual axes are read off the tracer's VMA.
    """
    r = _ACTIVE.get()
    if r is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = r.spec(*logical)
    try:
        manual = jax.typeof(x).vma
    except (AttributeError, TypeError):
        manual = frozenset()
    if manual:
        def strip(entry):
            if entry is None:
                return None
            if isinstance(entry, tuple):
                kept = tuple(a for a in entry if a not in manual)
                return kept if kept else None
            return None if entry in manual else entry
        spec = P(*(strip(e) for e in spec))
        # inside shard_map the constraint must carry the trace-time mesh,
        # whose manual axes are typed Manual
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(jax.sharding.get_abstract_mesh(), spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(r.mesh, spec))


def logical_sharding(*logical) -> Optional[NamedSharding]:
    """NamedSharding for the active rules (None outside a context)."""
    r = _ACTIVE.get()
    if r is None:
        return None
    return r.sharding(*logical)


def match_vma(x, ref):
    """Make ``x`` vary over the same manual axes as ``ref``.

    Under partial-manual shard_map (the compressed-gradient path), scan
    carries initialized from constants are VMA-invariant while the scanned
    computation is axis-varying; JAX requires carry in/out types to match.
    This pcasts the init to the reference's variance and is a no-op outside
    shard_map.  Applied where model code creates scan carries.
    """
    try:
        vma_ref = jax.typeof(ref).vma
        vma_x = jax.typeof(x).vma
    except (AttributeError, TypeError):
        return x
    need = tuple(a for a in vma_ref if a not in vma_x)
    if not need:
        return x
    return jax.lax.pcast(x, need, to="varying")


def match_vma_tree(tree, ref_leaf):
    return jax.tree.map(lambda t: match_vma(t, ref_leaf), tree)


def shard_attn_qkv(q, k, v):
    """Adaptive attention sharding for full-sequence (train/prefill) paths.

    q: [B,H,Sq,dh]; k/v: [B,G,Sk,*].  If the head count divides the model
    axis, shard heads (Megatron).  Otherwise shard the QUERY sequence over
    model and replicate K/V there (sequence-parallel attention): every
    score/softmax op stays local.  Without this, GSPMD partial-sums the
    f32 logits of misaligned-head archs over a subgroup -- 2.5 TB/step on
    qwen2-7b prefill (SS Perf, dense-cells fix).
    """
    r = _ACTIVE.get()
    if r is None:
        return q, k, v
    model = r.rules.get("model")
    if model is None:
        return q, k, v
    sizes = dict(zip(r.mesh.axis_names, r.mesh.devices.shape))
    msize = sizes.get(model, 1)
    B, H, Sq = q.shape[0], q.shape[1], q.shape[2]
    G = k.shape[1]
    if H % msize == 0 and G % msize == 0:
        q = shard(q, "batch", "model", None, None)
        k = shard(k, "batch", "model", None, None)
        v = shard(v, "batch", "model", None, None)
    elif Sq % msize == 0:
        q = shard(q, "batch", None, "model", None)
        k = shard(k, "batch", None, None, None)   # replicated over model
        v = shard(v, "batch", None, None, None)
    return q, k, v
