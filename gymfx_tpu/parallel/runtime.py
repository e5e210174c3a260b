"""Pod-scale sharded training runtime: ONE owner for mesh + placement.

Before this module every trainer carried its own copy of the placement
logic (PPO's private ``_shard_state``, IMPALA/portfolio duplicating the
same groups, PBT's ad-hoc ``_place``).  :class:`ShardedRuntime` centralizes the whole story:

  * the **mesh** (built here from ``mesh_shape`` config, or adopted);
  * the **NamedSharding plan** — one committed placement per state
    group, shared by all four trainers:

      ===============  =============================================
      group            placement
      ===============  =============================================
      params           wide 2-D matrices ``P(None, 'model')`` when
                       ``shape[-1] % model == 0`` and ``>= 128``
                       (tensor parallelism); everything else
                       replicated
      opt state / rng  replicated (``P()``)
      env batch        leading env axis ``P('data')`` (env states,
                       obs vectors, recurrent carries, trajectories)
      PBT population   leading member axis ``P('data')`` — members
                       are embarrassingly parallel between
                       exploit/explore syncs
      market data      replicated per streamed shard (every device's
                       env shard reads the full bar window)
      ===============  =============================================

  * **donated multi-chip supersteps**: the plan places the state once;
    the existing ``train/common.make_train_many`` driver (``jax.jit``
    + ``donate_argnums=0`` over a ``lax.scan`` of K fused steps) then
    runs as a single GSPMD program over the mesh — XLA inserts the
    gradient all-reduce over 'data' and the tensor-parallel collectives
    over 'model'; no per-device driver code exists anywhere;
  * **sharded host→device bar streaming**: :meth:`bar_streamer` builds
    a :class:`~gymfx_tpu.data.feed.BarStreamer` whose double-buffered
    ``shard_market_data`` shards are ``device_put`` with the mesh
    placement instead of landing on device 0 only;
  * **checkpoint round-trips**: restored host arrays re-enter the mesh
    placement through the same plan (:meth:`place_state`), so a resumed
    run is placed identically to the run that saved.

With ``mesh_shape`` unset the trainers hold no runtime at all
(``ShardedRuntime.from_config`` returns None) and their fast paths are
bit-for-bit the single-device ones.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gymfx_tpu.parallel.mesh import (
    batch_sharding,
    mesh_from_config,
    replicated_sharding,
    validate_batch_axis,
    validate_population_axis,
)


class StatePlan(NamedTuple):
    """Field-group placement plan for one trainer's state NamedTuple:
    which fields are policy parameters (tensor-shard candidates), which
    replicate, and which shard their leading env axis over 'data'."""

    params: Tuple[str, ...] = ()
    replicated: Tuple[str, ...] = ()
    batched: Tuple[str, ...] = ()


class ShardedRuntime:
    """Owns a live mesh and the shared NamedSharding placement plan."""

    def __init__(self, mesh: Mesh):
        if mesh is None:
            raise ValueError(
                "ShardedRuntime requires a mesh; with mesh_shape unset the "
                "trainers run the single-device fast path without a runtime"
            )
        self.mesh = mesh

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> Optional["ShardedRuntime"]:
        """Resolve the ``mesh_shape`` config key (honor-or-reject,
        parallel/mesh.mesh_from_config); None when unset — the callers
        keep their exact no-mesh fast path."""
        mesh = mesh_from_config(config)
        return None if mesh is None else cls(mesh)

    # ------------------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def mesh_shape(self) -> Dict[str, int]:
        return dict(self.mesh.shape)

    def validate_batch(self, n: int, what: str) -> None:
        validate_batch_axis(self.mesh, n, what)

    def validate_population(self, population: int) -> None:
        validate_population_axis(self.mesh, population)

    # -- shardings ------------------------------------------------------
    def replicated(self) -> NamedSharding:
        return replicated_sharding(self.mesh)

    def batched(self) -> NamedSharding:
        """Leading env (or population) axis over 'data'."""
        return batch_sharding(self.mesh)

    def _param_sharding(self, x: Any) -> NamedSharding:
        """Tensor-shard wide 2-D policy matrices over 'model'; replicate
        the rest (small/odd-shaped leaves all-gather more than they
        save)."""
        mesh = self.mesh
        if (
            "model" in mesh.axis_names
            and getattr(x, "ndim", 0) == 2
            and x.shape[-1] % mesh.shape["model"] == 0
            and x.shape[-1] >= 128
        ):
            return NamedSharding(mesh, P(None, "model"))
        return replicated_sharding(self.mesh)

    # -- placement ------------------------------------------------------
    def place_params(self, tree: Any) -> Any:
        return jax.tree.map(
            lambda x: jax.device_put(x, self._param_sharding(x)), tree
        )

    def place_replicated(self, tree: Any) -> Any:
        rep = self.replicated()
        return jax.tree.map(
            lambda x: jax.device_put(x, rep) if hasattr(x, "shape") else x,
            tree,
        )

    def _batched_or_rep(self, x: Any, batch: NamedSharding,
                        rep: NamedSharding) -> NamedSharding:
        # zero-sized leaves (e.g. an empty feat_window feature column)
        # come back REPLICATED from every compiled program regardless of
        # the input spec; placing them P('data') would make the AOT
        # executables reject their own output on the next call
        return rep if getattr(x, "size", 1) == 0 else batch

    def place_batched(self, tree: Any) -> Any:
        batch, rep = self.batched(), self.replicated()
        return jax.tree.map(
            lambda x: jax.device_put(x, self._batched_or_rep(x, batch, rep)),
            tree,
        )

    def place_groups(
        self,
        *,
        params: Optional[Dict[str, Any]] = None,
        replicated: Optional[Dict[str, Any]] = None,
        batched: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Place named field groups; returns ``{field: placed_tree}``."""
        out: Dict[str, Any] = {}
        for name, tree in (params or {}).items():
            out[name] = self.place_params(tree)
        for name, tree in (replicated or {}).items():
            out[name] = self.place_replicated(tree)
        for name, tree in (batched or {}).items():
            out[name] = self.place_batched(tree)
        return out

    def place_state(self, state: Any, plan: StatePlan) -> Any:
        """Place a trainer state NamedTuple per its :class:`StatePlan`.
        Used at init AND on checkpoint restore: host arrays loaded from
        a checkpoint re-enter the exact mesh placement the saving run
        used, so resume is placement-identical."""
        groups = self.place_groups(
            params={f: getattr(state, f) for f in plan.params},
            replicated={f: getattr(state, f) for f in plan.replicated},
            batched={f: getattr(state, f) for f in plan.batched},
        )
        return state._replace(**groups)

    def place_population(self, states: Any) -> Any:
        """Shard a vmapped population state (leading member axis) over
        'data': P members train on P/devices chips each.  Non-array
        leaves (e.g. injected-hyperparameter callables inside the
        optimizer state) pass through."""
        pop, rep = self.batched(), self.replicated()
        return jax.tree.map(
            lambda x: jax.device_put(x, self._batched_or_rep(x, pop, rep))
            if hasattr(x, "shape") else x,
            states,
        )

    def place_market_data(self, data: Any) -> Any:
        """Replicate a (host) MarketData shard onto every mesh device —
        each device's env shard reads the full bar window, and without
        an explicit placement ``jax.device_put`` lands host arrays on
        device 0 only (forcing an implicit transfer inside the sharded
        rollout program)."""
        rep = self.replicated()
        return jax.tree.map(lambda x: jax.device_put(x, rep), data)

    def bar_streamer(self, host_data: Any, *, window_size: int,
                     budget_mb: float, min_shard_bars: int = 64,
                     compress: str = "off", tick_size: float = 1e-5):
        """A double-buffered :class:`~gymfx_tpu.data.feed.BarStreamer`
        whose ``shard_market_data`` shards are placed across the mesh
        (host→device DMA of shard ``t+1`` still overlaps compute on
        shard ``t``; only the placement target changes).  With
        ``compress`` on, the int16 tapes ride the same placement and the
        fused decode materializes each replicated f32 shard on device
        (data/compress.py)."""
        from gymfx_tpu.data.feed import BarStreamer

        return BarStreamer(
            host_data, window_size=window_size, budget_mb=budget_mb,
            min_shard_bars=min_shard_bars, placement=self.replicated(),
            compress=compress, tick_size=tick_size,
        )

    # ------------------------------------------------------------------
    def describe(self) -> Dict[str, Any]:
        """Summary/docs slice: the mesh and the committed plan."""
        return {
            "mesh_shape": self.mesh_shape,
            "n_devices": self.n_devices,
            "plan": {
                "params": "wide 2-D matrices P(None,'model') "
                          "(last dim % model == 0 and >= 128); "
                          "rest replicated",
                "opt_state": "replicated",
                "env_batch": "P('data') on the leading env axis",
                "population": "P('data') on the leading member axis (PBT)",
                "market_data": "replicated per streamed shard",
            },
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"ShardedRuntime(mesh_shape={self.mesh_shape})"
