"""Mesh-sharded CG request router — serving on the production topology.

``CGRequestRouter`` simulates the paper's distributed sources as a vmap
axis in one process; this router puts them on a JAX device mesh
(``launch.mesh.make_source_mesh``): each host owns its
``delta [S_local, n_bins]`` lane, per-block routing runs under
``shard_map`` and the ``sync_every`` delta-merge is a ``jax.lax.psum``
across the ``sources`` axis (``kernels.mesh``). Routing is
bit-identical to the vmapped engine at matching config — CI gates the
``sync_every=1`` case.

The VW→replica owner map is the other piece of shared state: it
replicates across the mesh through a ``delegation.VersionedOwnerMap``.
Every rebalance/evacuation *commits* a new version atomically;
``owner_sync_every`` commits later (1 = immediately) the routers
*adopt* it. Until adoption the submit path gathers owners from the
base snapshot — a stale router routes on the pre-move map, which is
merely conservative, never torn. Forced updates (evacuation, an
explicit ``vw_owner`` assignment, restores) adopt immediately: routing
to a dead replica is a correctness problem, a missed rebalance move is
not.

Usage::

    mesh = make_source_mesh()            # all local devices
    router = MeshCGRequestRouter(n_replicas=4, n_sources=8, mesh=mesh)
    engine = ServingEngine(fns, router, async_submit=True)

See docs/multihost.md for the mesh layout and the 8-host demo
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.core import delegation
from repro.kernels.mesh import (SOURCES_AXIS, mesh_porc_multisource,
                                shard_multisource_state)
from repro.launch.mesh import make_source_mesh
from repro.serve.engine import CGRequestRouter


@dataclass
class MeshCGRequestRouter(CGRequestRouter):
    """``CGRequestRouter`` with source lanes and routing state on a
    device mesh. Drop-in for the single-host router wherever
    ``hh_scheme`` is off; ``n_sources`` must be a multiple of the
    mesh's host count (each host owns ``n_sources / H`` lanes).

    ``mesh`` defaults to a fresh 1-D ``("sources",)`` mesh over every
    local device; ``owner_sync_every`` is how many rebalance commits a
    router may lag the owner map before adopting (1 = every commit,
    single-host parity).
    """
    mesh: object = None
    owner_sync_every: int = 1

    def __post_init__(self):
        if self.hh_scheme:
            raise NotImplementedError(
                "heavy-hitter probe policies are not mesh-sharded yet; "
                "use CGRequestRouter for hh_scheme routing")
        super().__post_init__()
        if self.mesh is None:
            self.mesh = make_source_mesh()
        H = self.mesh.shape[SOURCES_AXIS]
        if self.n_sources % H:
            raise ValueError(
                f"n_sources={self.n_sources} must be a multiple of the "
                f"mesh's {H} hosts (each host owns n_sources/H lanes)")
        self._state = shard_multisource_state(self._state, self.mesh)
        self._omap = delegation.VersionedOwnerMap(self._dstate.vw_owner,
                                                  mesh=self.mesh)
        self._commits_behind = 0

    # -- versioned owner propagation --------------------------------------
    @property
    def owner_version(self) -> int:
        """Version of the latest committed owner map (monotonic)."""
        return self._omap.version

    @property
    def owner_adopted_version(self) -> int:
        """Version the routers are currently routing against."""
        return self._omap.base_version

    def _owner_view(self):
        # the snapshot a router at the adopted version sees: the head
        # when fully synced, otherwise the base fallback
        return self._omap.view(self._omap.base_version)

    def _note_owner_update(self, force: bool = False) -> None:
        self._omap.commit(self._dstate.vw_owner)
        self._commits_behind += 1
        if force or self._commits_behind >= self.owner_sync_every:
            self._omap.adopt()
            self._commits_behind = 0

    # -- sharded submit path ----------------------------------------------
    def _route_keys(self, keys):
        """The routing launch of ``dispatch_batch``, on the mesh: the
        batch splits round-robin across the source lanes, each host
        routes its lanes against base + its own deltas under
        ``shard_map``, and the delta-merge is a psum over the
        ``sources`` axis."""
        return mesh_porc_multisource(
            keys, self.n_virtual, self.mesh, n_sources=self.n_sources,
            sync_every=self.sync_every, block=self.block_size, eps=self.eps,
            state=self._state)
