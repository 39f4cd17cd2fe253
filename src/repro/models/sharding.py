"""Logical-axis sharding with divisibility-aware resolution.

Every parameter / activation dimension carries a *logical* axis name
(``"batch"``, ``"heads"``, ``"vocab"``, ...).  A :class:`Rules` table maps each
logical axis to an ordered list of candidate mesh-axis tuples; the resolver
picks the first candidate that

  * exists in the mesh,
  * evenly divides the dimension (XLA rejects non-divisible explicit
    shardings — verified on jax 0.8.2), and
  * does not reuse a mesh axis already consumed by another dimension of the
    same tensor,

falling back to replication otherwise.  Every fallback is recorded so the
dry-run can report exactly which tensors lost which sharding (e.g. the
24-head phi4 attention on a 16-way ``model`` axis).
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = tuple[Optional[str], ...]  # logical axes of one tensor (None = replicated dim)


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# logical axis -> ordered candidates, each a tuple of mesh axis names.
# () means "replicate".  The FIRST feasible candidate wins.
DEFAULT_RULES: dict[str, tuple[tuple[str, ...], ...]] = {
    # activations
    "batch": (("pod", "data"), ("data",), ()),
    "seq": ((),),
    "seq_q": ((),),             # overridden to ("model",) when heads unshardable
    "kv_seq": ((),),            # overridden to ("data",) for long-context decode
    "layers": ((),),            # scan-stacked layer dim (ZeRO may claim it)
    "embed": ((),),
    "act_heads": (("model",), ()),
    "act_ffn": (("model",), ()),
    # parameters
    "vocab": (("model",), ()),
    "heads": (("model",), ()),
    "kv_heads": (("model",), ()),
    "head_dim": ((),),
    "ffn": (("model",), ()),
    "experts": (("model",), ()),
    "expert_ffn": ((),),
    "expert_embed": ((),),
    # explicit-EP (shard_map a2a) weight layout
    "experts_ep": (("data",), ()),
    "expert_ffn_ep": (("model",), ()),
    "conv": ((),),
    "ssm_state": ((),),
    "dt": (("model",), ()),     # per-head dt/A params follow head sharding
    "frontend": ((),),
    "patches": ((),),
}

# ZeRO-1: additionally shard optimizer state over the data axis on the first
# dimension that accepts it (applied on top of the parameter spec).
ZERO_AXES = ("data",)


@dataclass
class Drop:
    """One sharding fallback event (for the dry-run report)."""

    tensor: str
    dim: int
    logical: str
    wanted: tuple[str, ...]
    size: int
    reason: str

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"{self.tensor}[dim{self.dim}:{self.logical}={self.size}] "
            f"dropped {self.wanted}: {self.reason}"
        )


@dataclass
class ShardingCtx:
    """Active (mesh, rules) pair used by model code via ``shard_hint``."""

    mesh: Mesh
    rules: dict[str, tuple[tuple[str, ...], ...]] = field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )
    drops: list[Drop] = field(default_factory=list)
    zero1: bool = False

    # -- resolution ---------------------------------------------------------

    def spec_for(
        self, axes: Axes, shape: Sequence[int], name: str = "?"
    ) -> P:
        mesh_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        used: set[str] = set()
        parts: list = []
        for dim, (logical, size) in enumerate(zip(axes, shape)):
            if logical is None:
                parts.append(None)
                continue
            candidates = self.rules.get(logical)
            if candidates is None:
                raise KeyError(
                    f"no sharding rule for logical axis {logical!r} "
                    f"(tensor {name})"
                )
            chosen: tuple[str, ...] = ()
            first_wanted: tuple[str, ...] = ()
            reason = ""
            for cand in candidates:
                if not cand:
                    chosen = ()
                    break
                if not first_wanted:
                    first_wanted = cand
                missing = [a for a in cand if a not in mesh_sizes]
                if missing:
                    reason = f"mesh axis {missing} absent"
                    continue
                prod = 1
                for a in cand:
                    prod *= mesh_sizes[a]
                if size % prod != 0:
                    reason = f"{size} % {prod} != 0"
                    continue
                if any(a in used for a in cand):
                    reason = "mesh axis already used in this tensor"
                    continue
                chosen = cand
                break
            if not chosen and first_wanted:
                self.drops.append(
                    Drop(name, dim, logical, first_wanted, size, reason)
                )
            used.update(chosen)
            if len(chosen) == 0:
                parts.append(None)
            elif len(chosen) == 1:
                parts.append(chosen[0])
            else:
                parts.append(tuple(chosen))
        return P(*parts)

    def sharding_for(
        self, axes: Axes, shape: Sequence[int], name: str = "?"
    ) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(axes, shape, name))

    def zero_spec_for(self, axes: Axes, shape: Sequence[int], name: str = "?") -> P:
        """Parameter spec with ZeRO-1 data-axis sharding stacked on top."""
        base = self.spec_for(axes, shape, name)
        mesh_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        parts = list(base) + [None] * (len(shape) - len(base))
        used = {a for p in parts if p for a in ((p,) if isinstance(p, str) else p)}
        for za in ZERO_AXES:
            if za in used or za not in mesh_sizes:
                continue
            # attach to the largest still-divisible dim
            best, best_size = -1, 0
            for i, (p, size) in enumerate(zip(parts, shape)):
                cur = 1
                if p:
                    for a in (p,) if isinstance(p, str) else p:
                        cur *= mesh_sizes[a]
                if size % (cur * mesh_sizes[za]) == 0 and size // cur > best_size:
                    best, best_size = i, size // cur
            if best >= 0:
                p = parts[best]
                if p is None:
                    parts[best] = za
                elif isinstance(p, str):
                    parts[best] = (p, za)
                else:
                    parts[best] = tuple(p) + (za,)
                used.add(za)
        return P(*parts)


def data_axis_size(mesh: Mesh) -> int:
    """Number of data-parallel replicas a mesh realizes (pod x data).

    The dp width the compressed-gradient layer needs: the leading axis of
    ``TrainState.comp_state`` residual leaves, the divisor of the
    compressed all-reduce mean, and the replica count in comm reports.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)


# ---------------------------------------------------------------------------
# Context plumbing
# ---------------------------------------------------------------------------

_state = threading.local()


def current_ctx() -> Optional[ShardingCtx]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_sharding(ctx: Optional[ShardingCtx]):
    prev = current_ctx()
    _state.ctx = ctx
    try:
        yield ctx
    finally:
        _state.ctx = prev


def make_ctx(
    mesh: Mesh,
    overrides: Optional[dict[str, tuple[tuple[str, ...], ...]]] = None,
    zero1: bool = False,
) -> ShardingCtx:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return ShardingCtx(mesh=mesh, rules=rules, zero1=zero1)


def shard_hint(x: jax.Array, axes: Axes, name: str = "act"):
    """``with_sharding_constraint`` against the active rules; no-op outside a
    sharding context (so smoke tests on one device run the same code)."""
    ctx = current_ctx()
    if ctx is None:
        return x
    spec = ctx.spec_for(axes, x.shape, name)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec)
    )


# ---------------------------------------------------------------------------
# Tree helpers: resolve a whole parameter tree
# ---------------------------------------------------------------------------


def tree_specs(ctx: ShardingCtx, shapes, axes_tree, zero1: bool = False):
    """Map a (shapes, logical-axes) tree pair to PartitionSpecs.

    ``shapes`` is any pytree of objects with ``.shape`` (arrays or
    ShapeDtypeStructs); ``axes_tree`` mirrors it with ``Axes`` tuples.
    ``zero1`` stacks data-axis sharding on every leaf
    (:meth:`ShardingCtx.zero_spec_for`).
    """

    def one(path, leaf, axes):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if zero1:
            return ctx.zero_spec_for(axes, leaf.shape, name)
        return ctx.spec_for(axes, leaf.shape, name)

    is_axes = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x
    )
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    axes_leaves = jax.tree_util.tree_leaves(axes_tree, is_leaf=is_axes)
    assert len(paths) == len(axes_leaves), (
        f"params/axes tree mismatch: {len(paths)} vs {len(axes_leaves)}"
    )
    specs = [one(p, l, a) for (p, l), a in zip(paths, axes_leaves)]
    treedef = jax.tree_util.tree_structure(shapes)
    return jax.tree_util.tree_unflatten(treedef, specs)


def tree_shardings(ctx: ShardingCtx, shapes, axes_tree, zero1: bool = False):
    specs = tree_specs(ctx, shapes, axes_tree, zero1=zero1)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
