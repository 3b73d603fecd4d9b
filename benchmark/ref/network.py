"""Road network: the framework's replacement for Valhalla routing tiles.

The reference consumes Valhalla ``.gph`` tiles read-only through the C++
matcher (reference: Dockerfile:42-49, py/reporter_service.py:21); this
framework owns its graph format instead: a columnar, numpy-backed directed
graph with OSMLR segment associations, stored as ``.npz`` tiles keyed by the
3-level geographic tile hierarchy in :mod:`reporter_tpu.core.tiles`.

Columnar layout (structure-of-arrays) is deliberate: candidate lookup and
route-distance queries touch millions of edges per probe batch, and flat
arrays let both the numpy fallback and the C++ host runtime iterate without
pointer chasing — and hand fixed-width tensors straight to the device.

Edges are directed; geometry is the straight segment between end nodes
(synthetic networks are built at block granularity so this is exact; dense
polyline shapes can be added by splitting edges).

OSMLR association: each edge belongs to at most one OSMLR traffic segment
(``edge_segment_id``; -1 when unassociated, e.g. service roads), entering it
at ``edge_segment_offset_m`` from the segment start. A segment is a chain of
edges; ``segment_length_m`` maps segment id -> full length, which reporting
needs to distinguish complete from partial traversals
(reference: README.md "Reporter Output", length=-1 semantics).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from .geo import local_meters_projection


@dataclass
class EdgeAttr:
    """Convenience view of one edge's attributes."""
    edge_id: int
    start_node: int
    end_node: int
    length_m: float
    speed_kph: float
    segment_id: int          # -1 if no OSMLR association
    segment_offset_m: float  # distance from segment start at edge begin
    internal: bool           # turn channel / internal intersection / roundabout


@dataclass
class RoadNetwork:
    # nodes
    node_lat: np.ndarray  # (N,) f64 degrees
    node_lon: np.ndarray  # (N,) f64
    # directed edges
    edge_start: np.ndarray        # (E,) i32 node index
    edge_end: np.ndarray          # (E,) i32
    edge_length_m: np.ndarray     # (E,) f32
    edge_speed_kph: np.ndarray    # (E,) f32
    edge_segment_id: np.ndarray   # (E,) i64, -1 = unassociated
    edge_segment_offset_m: np.ndarray  # (E,) f32
    edge_internal: np.ndarray     # (E,) bool
    # OSMLR segment id -> total segment length (meters)
    segment_length_m: Dict[int, float] = field(default_factory=dict)

    # derived, built lazily
    _csr_offsets: Optional[np.ndarray] = None   # (N+1,) out-edge CSR
    _csr_edges: Optional[np.ndarray] = None     # (E,) edge ids sorted by start node
    _node_x: Optional[np.ndarray] = None        # projected meters
    _node_y: Optional[np.ndarray] = None
    _proj: Optional[tuple] = None               # (to_xy, to_ll)
    _anchor: Optional[tuple] = None             # (lat0, lon0)
    _headings: Optional[np.ndarray] = None      # (E, 2) unit headings

    @property
    def num_nodes(self) -> int:
        return len(self.node_lat)

    @property
    def num_edges(self) -> int:
        return len(self.edge_start)

    # ---- projection ------------------------------------------------------
    def projection_anchor(self):
        """(lat0, lon0) the local projection is anchored at — the network
        centroid. Exposed so the native batched prep can project points
        with the identical chart (native/__init__.py prepare_batch)."""
        if self._anchor is None:
            self._anchor = (float(np.mean(self.node_lat)),
                            float(np.mean(self.node_lon)))
        return self._anchor

    def projection(self):
        """Local equirectangular meters projection anchored at the network
        centroid; built once and shared by spatial index and matcher."""
        if self._proj is None:
            self._proj = local_meters_projection(*self.projection_anchor())
        return self._proj

    def node_xy(self):
        if self._node_x is None:
            to_xy, _ = self.projection()
            self._node_x, self._node_y = to_xy(self.node_lat, self.node_lon)
        return self._node_x, self._node_y

    def headings(self) -> np.ndarray:
        """(E, 2) unit heading per edge in projected meters
        (straight-segment geometry, matching the native runtime's
        head_x/head_y); cached — turn-penalty pricing and its removal in
        assembly both read this per decoded transition."""
        if self._headings is None:
            nx, ny = self.node_xy()
            dx = nx[self.edge_end] - nx[self.edge_start]
            dy = ny[self.edge_end] - ny[self.edge_start]
            n = np.maximum(np.hypot(dx, dy), 1e-9)
            self._headings = np.stack([dx / n, dy / n], axis=1)
        return self._headings

    # ---- adjacency -------------------------------------------------------
    def csr(self):
        """Out-edge adjacency in CSR form: (offsets[N+1], edge_ids[E])."""
        if self._csr_offsets is None:
            order = np.argsort(self.edge_start, kind="stable")
            counts = np.bincount(self.edge_start, minlength=self.num_nodes)
            offsets = np.zeros(self.num_nodes + 1, dtype=np.int64)
            np.cumsum(counts, out=offsets[1:])
            self._csr_offsets = offsets
            self._csr_edges = order.astype(np.int32)
        return self._csr_offsets, self._csr_edges

    def edge(self, edge_id: int) -> EdgeAttr:
        return EdgeAttr(
            edge_id=edge_id,
            start_node=int(self.edge_start[edge_id]),
            end_node=int(self.edge_end[edge_id]),
            length_m=float(self.edge_length_m[edge_id]),
            speed_kph=float(self.edge_speed_kph[edge_id]),
            segment_id=int(self.edge_segment_id[edge_id]),
            segment_offset_m=float(self.edge_segment_offset_m[edge_id]),
            internal=bool(self.edge_internal[edge_id]),
        )

    # ---- persistence (our .npz tile format) ------------------------------
    def save(self, path: str) -> None:
        seg_ids = np.array(sorted(self.segment_length_m), dtype=np.int64)
        seg_lens = np.array([self.segment_length_m[s] for s in seg_ids],
                            dtype=np.float32)
        np.savez_compressed(
            path,
            node_lat=self.node_lat, node_lon=self.node_lon,
            edge_start=self.edge_start, edge_end=self.edge_end,
            edge_length_m=self.edge_length_m,
            edge_speed_kph=self.edge_speed_kph,
            edge_segment_id=self.edge_segment_id,
            edge_segment_offset_m=self.edge_segment_offset_m,
            edge_internal=self.edge_internal,
            seg_ids=seg_ids, seg_lens=seg_lens,
        )

    @classmethod
    def load(cls, path) -> "RoadNetwork":
        data = np.load(path)
        seg = dict(zip(data["seg_ids"].tolist(), data["seg_lens"].tolist()))
        return cls(
            node_lat=data["node_lat"], node_lon=data["node_lon"],
            edge_start=data["edge_start"], edge_end=data["edge_end"],
            edge_length_m=data["edge_length_m"],
            edge_speed_kph=data["edge_speed_kph"],
            edge_segment_id=data["edge_segment_id"],
            edge_segment_offset_m=data["edge_segment_offset_m"],
            edge_internal=data["edge_internal"],
            segment_length_m=seg,
        )
