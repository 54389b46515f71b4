"""Device mesh construction and sharding policies for distributed
tree learning.

TPU-native replacement for the reference's entire network stack
(reference: src/network/ Linkers + Bruck/recursive-halving/ring
collectives, network.cpp:64-314, and the tree_learner x device dispatch
tree_learner.cpp:9-33).  The hand-written socket/MPI collectives
disappear: parallelism is expressed as shardings over a
``jax.sharding.Mesh`` and XLA inserts the psum / reduce-scatter /
all-gather over ICI/DCN:

  * ``data`` learner  — rows sharded (DataParallelTreeLearner,
    data_parallel_tree_learner.cpp): the histogram matmul contracts the
    sharded row dimension, XLA emits exactly the ReduceScatter(+gather)
    of per-(leaf,group,bin) partial histograms the reference codes by
    hand (:147-162); constraining the histogram output to be
    feature-sharded reproduces the per-machine feature ownership.
  * ``feature`` learner — bins replicated, histogram columns sharded
    (FeatureParallelTreeLearner): split search is divided by feature,
    the global best split is a tiny argmax all-reduce
    (SyncUpGlobalBestSplit, parallel_tree_learner.h:184-207) that XLA
    derives from the replicated argmax.
  * ``voting`` learner — top-k gain preselection then a reduced
    histogram exchange (voting_parallel_tree_learner.cpp); expressed
    with the same constraints plus a top_k mask.

Multi-host: call ``jax.distributed.initialize()`` before building the
mesh; the same jitted program then spans hosts with collectives routed
over ICI within a pod and DCN across pods.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..telemetry import TELEMETRY

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def build_mesh(config: Config) -> Optional[Mesh]:
    """Build the training mesh from config (mesh_shape/mesh_axes or all
    local devices on one axis matching the tree_learner)."""
    if config.tree_learner == "serial" and not config.mesh_shape:
        return None
    devices = jax.devices()
    if config.mesh_shape:
        shape = tuple(config.mesh_shape)
        axes = tuple(config.mesh_axes) or (DATA_AXIS,)
        n = int(np.prod(shape))
        if n > len(devices):
            # a requested mesh that cannot be built is an error: a
            # serial run under a distributed config's name would hide
            # the missing devices from whoever reads the result
            raise ValueError(
                f"mesh_shape {shape} needs {n} devices, jax.devices() "
                f"has {len(devices)}")
        return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
    n = len(devices)
    if n == 1:
        return None
    axis = FEATURE_AXIS if config.tree_learner == "feature" else DATA_AXIS
    return Mesh(np.asarray(devices), (axis,))


class ShardingPolicy:
    """Per-learner sharding decisions consumed by the grower."""

    def __init__(self, config: Config, mesh: Optional[Mesh]):
        self.mesh = mesh
        self.learner = config.tree_learner
        try:
            self.nproc = jax.process_count()
        except Exception:  # pragma: no cover - uninitialized backend
            self.nproc = 1
        # multi-host: arrays must be assembled from process-local
        # shards (device_put of a full array cannot address other
        # hosts' devices)
        self.multihost = mesh is not None and self.nproc > 1
        if TELEMETRY.on and mesh is not None:
            # topology gauges: a scraped metrics page should say what
            # fabric the run is on without reading logs
            TELEMETRY.gauge("mesh_devices", int(mesh.size))
            TELEMETRY.gauge("mesh_hosts", int(self.nproc))
            TELEMETRY.gauge("mesh_axes",
                            ",".join(f"{a}={n}" for a, n in
                                     zip(mesh.axis_names,
                                         mesh.devices.shape)))
        if mesh is None:
            self.row_spec = None
            self.hist_spec = None
            return
        axes = mesh.axis_names
        if self.learner in ("data", "voting") or DATA_AXIS in axes:
            data_axis = DATA_AXIS if DATA_AXIS in axes else axes[0]
            self.row_spec = P(data_axis)            # rows sharded
            # per-machine feature ownership after the reduce
            # (data_parallel_tree_learner.cpp:53-115): shard the reduced
            # histogram over groups so the row-contraction lowers to a
            # reduce-scatter instead of a full all-reduce
            self.hist_spec = P(None, data_axis, None, None)
        elif self.learner == "feature":
            f_axis = FEATURE_AXIS if FEATURE_AXIS in axes else axes[0]
            self.row_spec = None                    # rows replicated
            self.hist_spec = P(None, f_axis, None, None)
            # vertical partition (the reference's feature-parallel data
            # layout, feature_parallel_tree_learner.cpp): each device
            # owns its feature-group COLUMNS of the bin matrix, so the
            # histogram contraction is local per shard and only the
            # SplitInfo election + the owner's per-row routing decision
            # cross the network — without this, the SPMD partitioner
            # splits the replicated-bins contraction over rows and
            # all-reduces FULL histograms (caught by the
            # __graft_entry__ collective gate)
            self.bins_spec = P(None, f_axis)
        else:
            self.row_spec = None
            self.hist_spec = None

    # ------------------------------------------------------------------
    def place_bins(self, arr):
        """Place the (N, G) bin matrix: column-sharded for the
        feature-parallel learner (vertical partition) when the group
        count divides the mesh — the shard_map SplitInfo-election path
        needs even shards; uneven group counts fall back to the row
        placement (replicated bins, constraint-sharded histograms)."""
        spec = getattr(self, "bins_spec", None)
        if self.mesh is not None and spec is not None \
                and arr.shape[1] % self.mesh.size == 0:
            return jax.device_put(arr, NamedSharding(self.mesh, spec))
        return self.place_rows(arr)

    def place_rows(self, arr):
        """Place a row-indexed array ((N,) or (N, G)).  Multi-host: the
        array is the ASSEMBLED global view (host h's rows at
        [h*N/nproc, (h+1)*N/nproc)); this host's slice is extracted and
        the global array built from process-local shards."""
        if self.mesh is None or self.row_spec is None:
            return jax.device_put(arr)
        ndim = getattr(arr, "ndim", 1)
        spec = P(self.row_spec[0], *([None] * (ndim - 1)))
        if self.multihost:
            return self.place_local_rows(self._local_slice(arr, axis=0))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def place_local_rows(self, local_arr):
        """Multi-host: build the global row-sharded array from THIS
        host's padded shard (jax.make_array_from_process_local_data —
        the seam reference dataset_loader.cpp's pre-partitioned loading
        feeds)."""
        ndim = getattr(local_arr, "ndim", 1)
        spec = P(self.row_spec[0], *([None] * (ndim - 1)))
        sh = NamedSharding(self.mesh, spec)
        if not self.multihost:
            return jax.device_put(local_arr, sh)
        return jax.make_array_from_process_local_data(sh, local_arr)

    def place_row_shards(self, shard_arrays, n_padded: int):
        """Sharded-construct placement (lightgbm_tpu/sharded/): the
        per-participant row shards of the bin matrix go STRAIGHT onto
        their devices along the row mesh axis — device d receives its
        ``n_padded / mesh.size`` row block sliced from the shard list
        (plus the zero tail pad) and the global array assembles via
        ``jax.make_array_from_single_device_arrays``, so the host
        never materializes the concatenated matrix on the mesh path.
        The logical global layout is IDENTICAL to the single-matrix
        route (rows in construction order, pad at the tail): the
        compiled program, and therefore the trained trees, are
        byte-identical across the two routes.

        Falls back to a host concat (then the normal placement) when
        there is no 1-D row mesh to tile — serial runs, multi-axis
        meshes, the feature learner's vertical partition, multi-host
        (each host passes its own shards through
        ``place_local_rows``), or a row count the mesh can't divide."""
        arrs = [np.asarray(a) for a in shard_arrays]
        rest = tuple(arrs[0].shape[1:])
        n = sum(a.shape[0] for a in arrs)
        if n > n_padded:
            raise ValueError(f"shards hold {n} rows > n_padded "
                             f"{n_padded}")
        mesh = self.mesh
        direct = (mesh is not None and self.row_spec is not None
                  and not self.multihost
                  and len(mesh.axis_names) == 1
                  and getattr(self, "bins_spec", None) is None
                  and n_padded % mesh.size == 0)
        if not direct:
            full = np.zeros((n_padded,) + rest, dtype=arrs[0].dtype)
            full[:n] = np.concatenate(arrs) if len(arrs) > 1 else arrs[0]
            return self.place_bins(full) if full.ndim == 2 \
                else self.place_rows(full)
        spec = P(self.row_spec[0], *([None] * len(rest)))
        sh = NamedSharding(mesh, spec)
        shape = (n_padded,) + rest
        # shard start offsets within the logical global row order
        starts = np.cumsum([0] + [a.shape[0] for a in arrs])
        blocks = []
        for dev, idx in sh.addressable_devices_indices_map(
                shape).items():
            lo = idx[0].start or 0
            hi = idx[0].stop if idx[0].stop is not None else n_padded
            # stage (inside "upload"): a device's row block as one
            # host array, before the transfer
            with TELEMETRY.stage("shard_bins"):
                parts = []
                for i, a in enumerate(arrs):
                    s, e = max(lo, int(starts[i])), \
                        min(hi, int(starts[i + 1]))
                    if s < e:
                        parts.append(a[s - int(starts[i]):
                                       e - int(starts[i])])
                have = sum(p.shape[0] for p in parts)
                if have < hi - lo:      # zero tail pad on this device
                    parts.append(np.zeros((hi - lo - have,) + rest,
                                          dtype=arrs[0].dtype))
                block = np.ascontiguousarray(
                    parts[0] if len(parts) == 1
                    else np.concatenate(parts))
            blocks.append(jax.device_put(block, dev))
        return jax.make_array_from_single_device_arrays(shape, sh,
                                                        blocks)

    def place_score_rows(self, arr):
        """Place a (K, N) class-major score matrix (rows on axis 1)."""
        if self.mesh is None or self.row_spec is None:
            return jax.device_put(arr)
        sh = NamedSharding(self.mesh, P(None, self.row_spec[0]))
        if self.multihost:
            return jax.make_array_from_process_local_data(
                sh, self._local_slice(arr, axis=1))
        return jax.device_put(arr, sh)

    def _local_slice(self, arr, axis: int):
        import numpy as _np
        n = arr.shape[axis]
        per = n // self.nproc
        pid = jax.process_index()
        idx = [slice(None)] * arr.ndim
        idx[axis] = slice(pid * per, (pid + 1) * per)
        return _np.ascontiguousarray(_np.asarray(arr)[tuple(idx)])

    def replicate(self, arr):
        if self.mesh is None:
            return jax.device_put(arr)
        if self.multihost:
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P()), np.asarray(arr))
        return jax.device_put(arr, NamedSharding(self.mesh, P()))

    def constrain_hist(self, hist):
        """Apply the post-reduce histogram sharding constraint."""
        if self.mesh is None or self.hist_spec is None:
            return hist
        return jax.lax.with_sharding_constraint(
            hist, NamedSharding(self.mesh, self.hist_spec))

    @property
    def num_shards(self) -> int:
        return 1 if self.mesh is None else self.mesh.size
