"""Logical-axis sharding rules (MaxText-style), resolved per active mesh.

Model code annotates tensors with *logical* axis names ("batch", "heads",
"ff", …).  At trace time the names are resolved to the mesh axes that are
actually present — so the same model definition lowers correctly on the
single-pod (data=16, model=16) mesh, the multi-pod (pod=2, data=16,
model=16) mesh, and a single CPU device (no mesh → constraints are a
no-op, which is what the reduced-config smoke tests use).

Sharding scheme (DESIGN.md §6):
  batch     → ("pod", "data")   DP across pods and hosts
  fsdp      → "data"            parameter / optimizer-state FSDP shards
  heads     → "model"           TP over attention heads
  kv_heads  → "model"           TP over KV heads (when divisible)
  ff        → "model"           TP over FFN hidden
  vocab     → "model"           TP over embedding / logits vocab
  seq_mp    → "model"           sequence parallelism for the residual
                                stream / long KV caches
  expert    → "model"           expert parallelism (when divisible)
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = [
    "LOGICAL_RULES",
    "active_mesh",
    "constrain",
    "logical_to_spec",
    "param_sharding",
    "set_mesh",
    "with_logical_rules",
]

LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "seq_mp": ("model",),
    "replicated": (),
}

# ZeRO-3: pure FSDP over the flattened device grid — batch and parameter
# shards span BOTH axes, no tensor parallelism.  Attention/FFN compute is
# fully local; the only collectives are per-layer parameter (re)gathers.
# The right policy when TP would replicate compute (heads % mesh != 0).
ZERO3_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data", "model"),
    "fsdp": ("data", "model"),
    "heads": (),
    "kv_heads": (),
    "ff": (),
    "vocab": (),
    "expert": (),
    "seq_mp": (),
    "replicated": (),
}

POLICIES = {"dp_tp": LOGICAL_RULES, "zero3": ZERO3_RULES}

_local = threading.local()


def _rules():
    return getattr(_local, "rules", LOGICAL_RULES)


@contextlib.contextmanager
def with_logical_rules(overrides: dict[str, tuple[str, ...]]):
    """Temporarily override logical→mesh rules (perf experiments)."""
    old = _rules()
    _local.rules = {**old, **overrides}
    try:
        yield
    finally:
        _local.rules = old


def active_mesh():
    """The mesh in effect, or None.

    A ``with Mesh(...)`` context (the thread-local resource env that
    ``pjit``/``shard_map`` consult) wins and is returned as the concrete
    Mesh.  Otherwise the mesh ``set_mesh`` installed is returned as its
    ``AbstractMesh``: axis names and sizes but no ``.devices``, because
    only the abstract mesh may be read inside ``jax.jit``, where model
    code resolves its logical axes.  Callers that need device placement
    must check (see ``fleet.active_fleet_mesh``).
    """
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.thread_resources.env.physical_mesh
    if not mesh.empty:
        return mesh
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _mesh_axes():
    mesh = active_mesh()
    if mesh is None:
        return None
    return set(mesh.axis_names), {a: s for a, s in
                                  zip(mesh.axis_names, mesh.axis_sizes)}


_INSTALLED = None    # the jax.sharding.set_mesh handle set_mesh holds


def set_mesh(mesh):
    """Install ``mesh`` as the process-wide default; None clears it.

    Wraps ``jax.sharding.set_mesh``, which has no way to clear: the
    handle of the last install is kept, and clearing restores what it
    replaced.  Returns the mesh.
    """
    global _INSTALLED
    if _INSTALLED is not None:
        _INSTALLED.__exit__(None, None, None)
        _INSTALLED = None
    if mesh is not None:
        _INSTALLED = jax.sharding.set_mesh(mesh)
    return mesh


def logical_to_spec(*logical, shape=None) -> P | None:
    """Resolve logical axis names to a PartitionSpec for the active mesh.

    Each entry is a logical name, a tuple of logical names, or None.  Axes
    whose mesh axis is absent resolve to None; if ``shape`` is given, any
    dimension not divisible by its resolved mesh-axis product also
    resolves to None (graceful fallback, e.g. 60 experts on 16 devices).
    Returns None when no mesh is active.
    """
    present = _mesh_axes()
    if present is None:
        return None
    axes_set, axis_size = present
    rules = _rules()
    spec = []
    used: set[str] = set()
    for dim, name in enumerate(logical):
        if name is None:
            spec.append(None)
            continue
        names = name if isinstance(name, tuple) else (name,)
        mesh_axes: list[str] = []
        for n in names:
            for ax in rules.get(n, ()):  # logical → candidate mesh axes
                if ax in axes_set and ax not in used:
                    mesh_axes.append(ax)
        if shape is not None and mesh_axes:
            # greedy prefix fallback: if the full axis product does not
            # divide the dim, try shorter prefixes (e.g. a 151936-row
            # embedding shards 16-way over "data" when 256-way fails)
            while mesh_axes:
                total = int(np.prod([axis_size[a] for a in mesh_axes]))
                if shape[dim] % total == 0:
                    break
                mesh_axes = mesh_axes[:-1]
        used.update(mesh_axes)
        if not mesh_axes:
            spec.append(None)
        elif len(mesh_axes) == 1:
            spec.append(mesh_axes[0])
        else:
            spec.append(tuple(mesh_axes))
    return P(*spec)


def mesh_axis_size(axis: str) -> int:
    present = _mesh_axes()
    if present is None:
        return 1
    return present[1].get(axis, 1)


def heads_shardable(n_heads: int) -> bool:
    """True when TP over heads divides the model axis — otherwise
    attention falls back to sequence parallelism (context-parallel
    attention) so its compute still shards 'model'-ways."""
    m = mesh_axis_size("model")
    return n_heads % m == 0


def constrain(x, *logical):
    """with_sharding_constraint by logical names; no-op without a mesh.

    Extra logical entries beyond the array rank are dropped (so callers
    can annotate the common (B, S, d) pattern and still pass 2-D leaves).
    """
    spec = logical_to_spec(*logical[: x.ndim], shape=x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def param_sharding(path: str, shape) -> P | None:
    """Sharding spec for a parameter by naming convention.

    Conventions (see models/): parameter dict keys encode their role —
      wq/wk/wv/wo       attention projections
      w_gate/w_up/w_down FFN
      embed / unembed    vocab tables
      experts…           MoE stacks (leading expert dim)
    Everything 2D+ also gets FSDP on its largest remaining dim.
    """
    name = path.split("/")[-1]
    ndim = len(shape)

    def spec_of(*logical):
        return logical_to_spec(*logical, shape=shape)

    if ndim == 0:
        return spec_of()
    if name in ("embed", "unembed"):
        # (vocab, d_model) — vocab TP + FSDP on d_model
        return spec_of("vocab", "fsdp")
    if name in ("wq", "wk", "wv"):
        # (d_model, heads, head_dim) or stacked (L, d_model, H, hd)
        base = ("fsdp", "heads", None)
        return spec_of(*(((None,) * (ndim - 3)) + base))
    if name == "wo":
        base = ("heads", None, "fsdp")
        return spec_of(*(((None,) * (ndim - 3)) + base))
    if name in ("w_gate", "w_up"):
        base = ("fsdp", "ff")
        return spec_of(*(((None,) * (ndim - 2)) + base))
    if name == "w_down":
        base = ("ff", "fsdp")
        return spec_of(*(((None,) * (ndim - 2)) + base))
    if name.startswith("expert_"):
        # (…, E, d, f) stacks: expert-parallel when divisible, else TP on f
        if name.endswith("_down"):
            base = ("expert", "ff", "fsdp")
        else:
            base = ("expert", "fsdp", "ff")
        return spec_of(*(((None,) * (ndim - 3)) + base))
    if ndim >= 2:
        # generic 2D+: FSDP along the largest dim
        i = int(np.argmax(shape))
        logical = [None] * ndim
        logical[i] = "fsdp"
        return spec_of(*logical)
    return spec_of(*([None] * ndim))


def state_sharding(path: str, shape) -> P | None:
    """Sharding for decode-state leaves (KV caches, SSM states).

    KV caches (…, B, C, K, hd): batch-DP always; TP over KV heads when
    divisible, else over the cache length (flash-decoding style).  SSM
    states (…, B, di[, N]) and conv windows shard the feature dim.
    Leading stack dims (scan groups) stay unsharded.
    """
    present = _mesh_axes()
    if present is None:
        return None
    _, axis_size = present
    model = axis_size.get("model", 1)
    name = path.split("/")[-1]
    ndim = len(shape)

    def spec_of(*logical):
        return logical_to_spec(*logical, shape=shape)

    if name in ("k", "v") and ndim >= 4:
        K = shape[-2]
        if K % model == 0:
            base = ("batch", None, "kv_heads", None)
        else:
            base = ("batch", "seq_mp", None, None)
        return spec_of(*(((None,) * (ndim - 4)) + base))
    if name == "h" and ndim >= 2:
        if ndim >= 3 and shape[-1] <= 64:      # (…, B, di, N): shard di
            return spec_of(*((None,) * (ndim - 3) + ("batch", "ff", None)))
        return spec_of(*((None,) * (ndim - 2) + ("batch", "ff")))
    if name == "conv" and ndim >= 3:
        return spec_of(*((None,) * (ndim - 3) + ("batch", None, "ff")))
    if name == "pos":
        return spec_of()
    if ndim >= 4:                              # cross-attention K/V stacks
        return spec_of(*((None,) * (ndim - 4) + ("batch", None, None, None)))
    if ndim >= 1:
        return spec_of(*(("batch",) + (None,) * (ndim - 1)))
    return spec_of()
