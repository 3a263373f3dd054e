"""Federation mesh: maps N data stations onto the available JAX devices.

This is the TPU-native replacement for the reference's data plane
(vantage6-node daemons + Docker containers + HTTPS transport; SURVEY.md §1/§3).
Each *data station* owns a slice of a `jax.sharding.Mesh`; a federated round is
one jitted SPMD program in which "partial" functions run per-station under
`shard_map` and "central" aggregation lowers to XLA collectives over ICI.

Design (scales 1 chip -> full pod with one code path):

- All per-station state is *stacked* on a leading station axis: an array of
  shape ``[S, ...]`` holds every station's shard.
- The mesh has axes ``('station', 'device')``. The station mesh-axis size D is
  the largest divisor of S that fits the available devices; each of the D mesh
  slots simulates ``S/D`` stations via an inner ``vmap``. With D == S every
  station owns real devices; with D == 1 the same program runs on a laptop.
- ``fed_map(fn, ...)`` = ``shard_map(vmap(fn))`` over the station axis.
- Aggregation is expressed at the jnp level on station-sharded arrays
  (``jnp.sum(x, axis=0)``) so GSPMD inserts the all-reduce/reduce-scatter —
  the idiomatic XLA path — with explicit-collective variants in
  ``vantage6_tpu.fed`` where masking/secure-sum needs per-station RNG.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STATION_AXIS = "station"
DEVICE_AXIS = "device"


def station_shard_map(mesh: "FederationMesh", fn: Callable[..., Any],
                      in_specs: Any, out_specs: Any) -> Callable[..., Any]:
    """``shard_map`` over a FederationMesh with variance checking disabled
    (same rationale as ``fed_map``) — the entry point for explicit-collective
    code (``fed.collectives`` scattered primitives) that needs
    ``psum_scatter``/``all_gather`` with named-axis control instead of
    leaving the reduction to GSPMD."""
    return jax.shard_map(
        fn, mesh=mesh.mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


@dataclasses.dataclass(frozen=True)
class Station:
    """One data station (reference: a vantage6 node at an organization).

    In the reference a station is a daemon next to private data; here it is an
    index into the station axis of the federation mesh plus metadata. The
    privacy boundary is preserved *semantically* by the API (partials only see
    their own shard; only aggregates cross stations), not by physical network
    isolation — see docs/THREAT_MODEL.md for the honest mapping.
    """

    index: int
    name: str
    organization: str = ""
    databases: dict[str, Any] = dataclasses.field(default_factory=dict)


def _largest_divisor_leq(n: int, cap: int) -> int:
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


class FederationMesh:
    """Owns the device mesh and the station-axis execution primitives.

    Parameters
    ----------
    n_stations:
        Number of data stations S in the federation.
    devices:
        Flat list of JAX devices (default: ``jax.devices()``).
    devices_per_station:
        Devices forming each station's sub-mesh (tensor/model parallelism
        *within* a station rides the ``device`` mesh axis).
    """

    def __init__(
        self,
        n_stations: int,
        devices: Sequence[jax.Device] | None = None,
        devices_per_station: int = 1,
    ):
        if n_stations < 1:
            raise ValueError("n_stations must be >= 1")
        devices = list(devices if devices is not None else jax.devices())
        if devices_per_station < 1 or devices_per_station > len(devices):
            raise ValueError("invalid devices_per_station")
        self.n_stations = n_stations
        self.devices_per_station = devices_per_station
        usable = len(devices) // devices_per_station
        # Station mesh-axis size: largest divisor of S fitting the hardware.
        self.station_axis_size = _largest_divisor_leq(n_stations, usable)
        self.stations_per_slot = n_stations // self.station_axis_size
        n_used = self.station_axis_size * devices_per_station
        dev_array = np.array(devices[:n_used]).reshape(
            self.station_axis_size, devices_per_station
        )
        self.mesh = Mesh(dev_array, (STATION_AXIS, DEVICE_AXIS))

    # ------------------------------------------------------------------ specs
    def station_spec(self, *trailing: Any) -> P:
        """PartitionSpec sharding the leading (station) axis."""
        return P(STATION_AXIS, *trailing)

    def station_sharding(self, *trailing: Any) -> NamedSharding:
        return NamedSharding(self.mesh, self.station_spec(*trailing))

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_stacked(self, tree: Any) -> Any:
        """Place a pytree of stacked ``[S, ...]`` arrays onto the mesh,
        station axis sharded. Works for numpy or jax inputs; a host array
        goes shard by shard to its slot's device (never whole through
        device 0), an array already placed so is returned as is."""
        return jax.device_put(tree, self.station_sharding())

    def replicate(self, tree: Any) -> Any:
        """Place a pytree on every device of the mesh (same contract)."""
        return jax.device_put(tree, self.replicated_sharding())

    # ------------------------------------------------------------- execution
    def fed_map(
        self,
        fn: Callable[..., Any],
        *stacked_args: Any,
        replicated_args: tuple[Any, ...] = (),
    ) -> Any:
        """Run ``fn`` once per station; return stacked ``[S, ...]`` outputs.

        ``stacked_args`` are pytrees whose leaves carry a leading station axis
        of size S (sharded over the mesh's station axis). ``replicated_args``
        are broadcast to every station (e.g. the global model). This is the
        TPU-native analogue of the reference's "create one subtask per
        organization" fan-out (SURVEY.md §3.1) — but it is a single SPMD
        program, not N containers.
        """
        n_s = len(stacked_args)

        def block_fn(*args):
            s_args, r_args = args[:n_s], args[n_s:]
            # Each mesh slot holds a [S/D, ...] block of stations; the inner
            # vmap walks the stations within the block.
            return jax.vmap(lambda *sa: fn(*sa, *r_args))(*s_args)

        in_specs = tuple(self.station_spec() for _ in stacked_args) + tuple(
            P() for _ in replicated_args
        )
        # Variance checking OFF: station blocks are PURELY LOCAL programs.
        # With it on, replicated (P()) inputs are "unvarying" and jax
        # auto-inserts a psum over the mesh on any gradient taken w.r.t. them
        # inside the body — silently turning each station's local gradient
        # into the cross-station sum (breaking the federated privacy/
        # isolation contract, not just numerics). All cross-station reduction
        # happens explicitly, outside fed_map, via fed.collectives.
        return jax.shard_map(
            block_fn,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=self.station_spec(),
            check_vma=False,
        )(*stacked_args, *replicated_args)

    def fingerprint(self) -> tuple:
        """Hashable identity of everything a compiled runner depends on:
        station count, mesh factorization, and the exact device placement.
        Two meshes with equal fingerprints produce identical shardings, so
        jitted programs traced against one are reusable with the other —
        the key workload runner caches (glm/quantiles) use instead of mesh
        OBJECT identity, which would recompile (and leak a cache entry) for
        every fresh FederationMesh over the same devices."""
        return (
            self.n_stations,
            self.station_axis_size,
            self.devices_per_station,
            tuple(d.id for d in self.mesh.devices.flat),
        )

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"FederationMesh(S={self.n_stations}, "
            f"station_axis={self.station_axis_size}, "
            f"per_slot={self.stations_per_slot}, "
            f"dps={self.devices_per_station})"
        )
