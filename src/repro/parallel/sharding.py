"""Sharding policy: logical axis names -> mesh PartitionSpecs.

Every parameter / activation in the model zoo is annotated with a tuple of
*logical* axis names (one per dim, ``None`` = replicated).  ``MeshEnv`` maps
logical names onto the physical mesh axes:

  batch            -> all data-parallel axes ("pod","data") / ("data",)
  vocab/heads/ff/
  experts/dinner   -> "model"      (tensor / expert parallelism)
  embed            -> data axes    (FSDP: 2-D weight sharding so params,
                                    grads and optimizer state all scale
                                    with the full chip count)
  kv_heads         -> "model" when the arch's kv-head count divides the TP
                      degree, else replicated (the decode path then uses the
                      sequence-sharded flash-decode cache instead)
  seq_kv           -> "model"      (flash-decode: KV cache sharded on seq)

The env degrades gracefully to single-device smoke-test mode (mesh=None):
constraints become no-ops and shard_map paths fall back to plain jnp.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshEnv:
    """Physical mesh + the logical->physical axis mapping for one model."""

    mesh: Mesh | None = None
    data_axes: tuple[str, ...] = ("data",)     # DP + FSDP axes (includes "pod")
    model_axis: str | None = "model"
    # per-arch switches, decided from the config at construction time:
    shard_kv_heads: bool = False               # kv_heads % tp == 0
    flash_decode: bool = False                 # seq-shard the decode KV cache
    # Performance knobs (hillclimb levers, see EXPERIMENTS.md #Perf)
    remat: bool = True
    fsdp: bool = True                          # 2-D ("embed"->data) weight sharding

    # ------------------------------------------------------------------ #
    @property
    def tp(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def dp(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    # ------------------------------------------------------------------ #
    def _physical(self, logical: str | None):
        if logical is None:
            return None
        if logical == "batch":
            return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        if logical in ("vocab", "heads", "ff", "experts", "dinner", "seq_kv",
                       "seq"):
            # "seq": Megatron-style sequence parallelism — the residual
            # stream between layers is sharded over "model", so saved-for-
            # backward activations scale with the FULL chip count.  XLA
            # inserts the all-gather (into attention/MLP) and reduce-scatter
            # (out of them) this implies.
            return self.model_axis
        if logical == "embed":
            if not self.fsdp:
                return None
            return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        if logical == "kv_heads":
            return self.model_axis if self.shard_kv_heads else None
        if logical == "model":
            return self.model_axis
        if logical == "data":
            return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        raise ValueError(f"unknown logical axis {logical!r}")

    def spec(self, axes: tuple[str | None, ...]) -> P:
        return P(*[self._physical(a) for a in axes])

    def _axis_size(self, phys) -> int:
        if phys is None or self.mesh is None:
            return 1
        if isinstance(phys, tuple):
            n = 1
            for a in phys:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[phys]

    def spec_sized(self, axes: tuple[str | None, ...],
                   shape: tuple[int, ...]) -> P:
        """Like spec(), but any dim not divisible by its mesh extent falls
        back to replication (e.g. hymba's 25 heads on TP=16)."""
        phys = []
        for a, dim in zip(axes, shape):
            p = self._physical(a)
            if p is not None and dim % self._axis_size(p) != 0:
                p = None
            phys.append(p)
        return P(*phys)

    def sharding(self, axes: tuple[str | None, ...],
                 shape: tuple[int, ...] | None = None) -> NamedSharding | None:
        if self.mesh is None:
            return None
        spec = self.spec(axes) if shape is None else self.spec_sized(axes, shape)
        return NamedSharding(self.mesh, spec)

    def constrain(self, x: jax.Array, axes: tuple[str | None, ...]) -> jax.Array:
        """with_sharding_constraint that is a no-op off-mesh."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, self.sharding(axes, tuple(x.shape)))


def logical_to_spec(env: MeshEnv, axes_tree: Any) -> Any:
    """Map a pytree of logical-axes tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: env.spec(axes),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def param_shardings(env: MeshEnv, axes_tree: Any, sds_tree: Any = None) -> Any:
    """Pytree of NamedShardings (or None off-mesh) mirroring the param tree.

    When sds_tree (shapes) is given, non-divisible dims auto-replicate."""
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x)
    if env.mesh is None:
        return jax.tree.map(lambda _: None, axes_tree, is_leaf=is_axes)
    if sds_tree is None:
        return jax.tree.map(
            lambda axes: NamedSharding(env.mesh, env.spec(axes)),
            axes_tree, is_leaf=is_axes)
    flat_a, treedef = jax.tree.flatten(axes_tree, is_leaf=is_axes)
    flat_s = treedef.flatten_up_to(sds_tree)
    out = [NamedSharding(env.mesh, env.spec_sized(a, tuple(s.shape)))
           for a, s in zip(flat_a, flat_s)]
    return jax.tree.unflatten(treedef, out)


def make_env(cfg, mesh: Mesh | None, *, multi_pod: bool | None = None,
             fsdp: bool = True, remat: bool = True,
             flash_decode: bool | None = None,
             dp_only: bool = False) -> MeshEnv:
    """Build the MeshEnv for an architecture config on a given mesh.

    dp_only: fold the "model" axis into data parallelism (batch sharded over
    every mesh axis, params replicated/FSDP).  The right choice for small
    models (whisper-medium at TP=16 is collective-bound — EXPERIMENTS.md
    #Perf iteration W1)."""
    if mesh is None:
        return MeshEnv(mesh=None, data_axes=("data",), model_axis=None,
                       shard_kv_heads=False, flash_decode=False,
                       remat=remat, fsdp=False)
    names = mesh.axis_names
    if dp_only:
        return MeshEnv(mesh=mesh, data_axes=tuple(names), model_axis=None,
                       shard_kv_heads=False, flash_decode=False,
                       remat=remat, fsdp=fsdp)
    data_axes = tuple(a for a in names if a in ("pod", "data"))
    model_axis = "model" if "model" in names else None
    tp = mesh.shape[model_axis] if model_axis else 1
    n_kv = getattr(cfg, "n_kv", 0) or 0
    shard_kv = n_kv > 0 and tp > 0 and (n_kv % tp == 0)
    if flash_decode is None:
        # default: flash-decode whenever the kv heads don't divide TP
        flash_decode = (n_kv > 0) and not shard_kv
    return MeshEnv(mesh=mesh, data_axes=data_axes, model_axis=model_axis,
                   shard_kv_heads=shard_kv, flash_decode=flash_decode,
                   remat=remat, fsdp=fsdp)
