"""Rule-based sharding.

Two halves:

1. **Parameter specs** — ``param_specs(params)`` walks the param pytree and
   assigns a ``PartitionSpec`` per leaf from its path + shape, sharding the
   biggest dims over ("data", "model") FSDP×TP style, with a divisibility
   fallback (a dim that doesn't divide the mesh axis is replicated).

2. **Activation constraints** — model code calls
   ``constrain(x, "batch", None, "tensor")`` with *logical* axis names; a
   contextvar holds the active mesh + logical→mesh-axis rules.  Outside a
   mesh context (CPU unit tests) it is a no-op, so the same model code runs
   everywhere.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical activation axis -> mesh axes (tuple = sharded over several)
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),       # batch dim of activations
    "seq": None,                    # sequence: replicated by default
    "tensor": "model",              # d_ff / head-sharded dims
    "heads": "model",               # attention heads (guarded by
                                    # divisibility; else forced replicated)
    "embed": None,                  # d_model on activations: replicated
    "expert": "model",              # expert-parallel dim
    "vocab": "model",
}

_CTX: contextvars.ContextVar = contextvars.ContextVar("shard_rules", default=None)


@contextlib.contextmanager
def use_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, Any]] = None):
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    token = _CTX.set((mesh, merged) if mesh is not None else None)
    try:
        yield
    finally:
        _CTX.reset(token)


def activation_rules() -> Optional[Tuple[Mesh, Dict[str, Any]]]:
    return _CTX.get()


def _resolve(mesh: Mesh, rules: Dict[str, Any], names) -> P:
    axes = []
    for n in names:
        if n is None:
            axes.append(None)
            continue
        mapped = rules.get(n, None)
        if mapped is None:
            axes.append(None)
            continue
        if isinstance(mapped, str):
            mapped = (mapped,)
        present = tuple(a for a in mapped if a in mesh.axis_names)
        axes.append(present if len(present) > 1 else (present[0] if present else None))
    return P(*axes)


def constrain(x: jax.Array, *names) -> jax.Array:
    """Apply a logical sharding constraint; no-op outside a mesh context or
    when a named dim doesn't divide its mesh axes."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = _resolve(mesh, rules, names)
    # guards: drop constraints that don't divide, and duplicate mesh axes
    # (e.g. "expert" and "tensor" both mapping to "model" — first wins)
    fixed = []
    used: set = set()
    for dim, ax in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if ax is None:
            fixed.append(None)
            continue
        axt = (ax,) if isinstance(ax, str) else tuple(ax)
        if any(a in used for a in axt):
            fixed.append(None)
            continue
        size = 1
        for a in axt:
            size *= mesh.shape[a]
        if dim % size == 0:
            fixed.append(ax)
            used.update(axt)
        else:
            fixed.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


# ---------------------------------------------------------------------------
# gather-then-hash
# ---------------------------------------------------------------------------


def gather_tree_async(tree: Any) -> list:
    """``(path, leaf)`` of every leaf of ``tree`` in flatten order, with
    the copy to host memory of every ``jax.Array`` leaf already started,
    all of them before the caller waits on the first.

    This is the *gather* half of the gather-then-hash digest contract:
    ``np.asarray(leaf)`` then waits for that leaf's bytes alone and gives
    its globally-assembled value, reassembled from the addressable shards
    of a sharded array, so a digest over training state is invariant to
    the mesh shape and device layout the producer happened to run on (a
    1-device CPU node and an 8-way FSDP node must commit bit-identical
    ``state_digest``s for the same params).  Host leaves pass through."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for _, leaf in flat:
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()
    return flat


# ---------------------------------------------------------------------------
# parameter partitioning
# ---------------------------------------------------------------------------

# Path-regex rules.  Matched against "/"-joined pytree key paths.  Each rule
# gives logical axes per *trailing* dimension (leading scan/stack dims get
# None).  ("fsdp", "tensor") means dim -2 over data, dim -1 over model.
_PARAM_RULES = [
    (r"embed|unembed|pos_table",        ("tensor", "fsdp")),      # (V, d) / (P, d)
    (r"experts/(w1|w3)$",               ("expert", "fsdp", "tensor_in")),  # (E, d, f)
    (r"experts/w2$",                    ("expert", "tensor_in", "fsdp")),  # (E, f, d)
    (r"router",                         ("fsdp", None)),          # (d, E)
    (r"(wq|wk|wv|q_proj|k_proj|v_proj)$", ("fsdp", "tensor")),    # (d, H*hd)
    (r"(wo|o_proj|out_proj)$",          ("tensor", "fsdp")),      # (H*hd, d)
    (r"w1$|w3$|lru_in|gate_in",         ("fsdp", "tensor")),      # (d, f)
    (r"w2$|lru_out",                    ("tensor", "fsdp")),      # (f, d)
    (r"(tm_[rkvgw]|tm_out|cm_[rk])$",   ("fsdp", "tensor")),      # rwkv mats (d, d)/(d,f)
    (r"cm_v$",                          ("tensor", "fsdp")),      # (f, d)
    (r"conv",                           (None, "tensor")),
]

_LOGICAL_PARAM_AXES = {
    "fsdp": ("data",),
    "tensor": ("model",),
    "tensor_in": ("model",),   # secondary tensor dim — replicated by default
    "expert": ("model",),
    None: (),
}


def _spec_for(path: str, shape: Tuple[int, ...], mesh: Mesh,
              fsdp: bool, expert_axis: str = "model",
              fsdp_pod: bool = False) -> P:
    logical = None
    for pat, axes in _PARAM_RULES:
        if re.search(pat, path):
            logical = axes
            break
    if logical is None or not shape:
        return P()
    # align logical axes to the trailing dims; leading stack dims -> None
    n_lead = len(shape) - len(logical)
    if n_lead < 0:
        logical = logical[-len(shape):]
        n_lead = 0
    axes = [None] * n_lead
    used = set()
    for dim, name in zip(shape[n_lead:], logical):
        mesh_axes = _LOGICAL_PARAM_AXES.get(name, ())
        if name == "fsdp" and fsdp and fsdp_pod \
                and "pod" in mesh.axis_names and "pod" not in used \
                and "data" not in used \
                and dim % (mesh.shape["pod"] * mesh.shape["data"]) == 0:
            axes.append(("pod", "data"))
            used.update(("pod", "data"))
            continue
        if name == "expert":
            mesh_axes = (expert_axis,)
        if name == "fsdp" and not fsdp:
            mesh_axes = ()
        if name == "tensor_in":
            # secondary tensor dim: picks up "model" when the expert dim
            # moved to "data" (expert_axis lever), else blocked by `used`
            mesh_axes = ("model",)
        pick = None
        for a in mesh_axes:
            if a in mesh.axis_names and a not in used and dim % mesh.shape[a] == 0:
                pick = a
                used.add(a)
                break
        axes.append(pick)
    return P(*axes)


def param_specs(params: Any, mesh: Mesh, fsdp: bool = True,
                expert_axis: str = "model", fsdp_pod: bool = False) -> Any:
    """PartitionSpec pytree matching ``params`` (which may be arrays or
    ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(_spec_for(pstr, tuple(leaf.shape), mesh, fsdp,
                               expert_axis, fsdp_pod))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params: Any, mesh: Mesh, fsdp: bool = True,
                    expert_axis: str = "model") -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh, fsdp, expert_axis))


# ---------------------------------------------------------------------------
# batch / cache partitioning
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_specs(batch: Any, mesh: Mesh, global_batch: int) -> Any:
    """Shard the leading (batch) dim over (pod, data) when divisible."""
    ba = batch_axes(mesh)
    size = 1
    for a in ba:
        size *= mesh.shape[a]

    def spec(leaf):
        if leaf.ndim and leaf.shape[0] == global_batch \
                and global_batch % size == 0:
            return P(ba)
        return P()

    return jax.tree.map(spec, batch)


def cache_specs(cache: Any, mesh: Mesh, global_batch: int) -> Any:
    """KV-cache/state sharding: batch dim over (pod, data); the LAST dim
    divisible by the model axis gets "model" (head_dim / lru / state dims
    — never the ring-buffer length, which is dynamically indexed)."""
    ba = batch_axes(mesh)
    bsz = 1
    for a in ba:
        bsz *= mesh.shape[a]
    msz = mesh.shape["model"] if "model" in mesh.axis_names else 1

    def spec(leaf):
        axes = [None] * leaf.ndim
        b_at = None
        for i, d in enumerate(leaf.shape):
            if d == global_batch and global_batch % bsz == 0:
                axes[i] = ba
                b_at = i
                break
        if msz > 1 and leaf.ndim >= 2:
            for i in range(leaf.ndim - 1, -1, -1):
                if i != b_at and axes[i] is None \
                        and leaf.shape[i] % msz == 0 and leaf.shape[i] > 1:
                    axes[i] = "model"
                    break
        return P(*axes)

    return jax.tree.map(spec, cache)
