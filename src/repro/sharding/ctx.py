"""Logical-axis sharding context (MaxText-style rules, hand-rolled).

Model code annotates tensors with *logical* axis names ("batch", "embed",
"heads", "mlp", "experts", ...).  A ``ShardingCtx`` binds the current mesh
plus a logical->physical mapping; ``shard_constraint`` then applies
``with_sharding_constraint`` — or no-ops when no ctx is active (single-device
smoke tests run the exact same model code).

Divisibility guard: a logical axis only maps to a physical mesh axis when the
dimension size divides evenly; otherwise it silently falls back to
replication.  This is what makes e.g. gemma's single KV head (kv=1) lower
cleanly on a 16-wide model axis while qwen's 8 KV heads shard where they can.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Mapping, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Default logical -> physical mapping.  "pod" multiplies the batch axes when
# present (multi-pod meshes); tensor-parallel axes all map to "model".
DEFAULT_RULES: Mapping[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),  # replicated by default; long-context configs override
    "seq_shard": ("data",),  # explicit sequence parallelism
    # Params' embed dim shards over the data axis: FSDP/ZeRO-style — weights
    # and optimizer state distribute over BOTH mesh axes, gathered on use.
    "embed": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "lora": ("data",),  # MLA low-rank dims: FSDP-shard like embed
    "cache_seq": (),
    "cache_head_dim": (),  # decode fallback when kv_heads don't divide
    "qkv": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_mlp": (),
    "layers": (),
    "conv": (),
    "ssm_heads": ("model",),
    "state": (),
}


@dataclasses.dataclass
class ShardingCtx:
    mesh: Mesh
    rules: Mapping[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def axis_size(self, names: Sequence[str]) -> int:
        size = 1
        for nm in names:
            if nm in self.mesh.shape:
                size *= self.mesh.shape[nm]
        return size

    def spec(self, logical: Sequence[Optional[str]], dims: Sequence[int] | None = None) -> P:
        """PartitionSpec for a tuple of logical names (None = replicated).

        When ``dims`` is given, any logical axis whose physical shard count
        does not divide the dim size falls back to replication.  A mesh axis
        already claimed by an earlier dim is dropped (PartitionSpecs may not
        repeat axes) — earlier dims win.
        """
        parts = []
        used: set = set()
        for k, name in enumerate(logical):
            if name is None:
                parts.append(None)
                continue
            phys = tuple(
                a
                for a in self.rules.get(name, ())
                if a in self.mesh.shape and a not in used
            )
            if not phys:
                parts.append(None)
                continue
            if dims is not None:
                n = self.axis_size(phys)
                if n <= 1 or dims[k] % n != 0:
                    parts.append(None)
                    continue
            used.update(phys)
            parts.append(phys if len(phys) > 1 else phys[0])
        return P(*parts)

    def sharding(self, logical: Sequence[Optional[str]], dims=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, dims))


_local = threading.local()


def set_ctx(ctx: Optional[ShardingCtx]) -> None:
    _local.ctx = ctx


def current_ctx() -> Optional[ShardingCtx]:
    return getattr(_local, "ctx", None)


@contextlib.contextmanager
def use_ctx(ctx: Optional[ShardingCtx]):
    prev = current_ctx()
    set_ctx(ctx)
    try:
        yield ctx
    finally:
        set_ctx(prev)


def shard_constraint(x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
    """Annotate activation sharding; no-op without an active ctx."""
    ctx = current_ctx()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(logical, x.shape))


def logical_sharding(shape: Sequence[int], logical: Sequence[Optional[str]]):
    """NamedSharding for a param of known shape (used to build in_shardings)."""
    ctx = current_ctx()
    if ctx is None:
        return None
    return ctx.sharding(logical, tuple(shape))
