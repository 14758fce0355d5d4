"""MapReduce runtime on a JAX device mesh.

Hadoop concept → this runtime:

* InputSplit            → equal transaction shards along the ``data`` mesh axis
* Mapper + Combiner     → per-device support-count kernel over the local shard
                          (local sums never leave the device uncombined)
* shuffle + Reducer     → one ``jax.lax.psum`` over the ``data`` axis
* one MapReduce *job*   → one jitted ``shard_map`` dispatch

The runtime tracks dispatch and compile counts: the paper's objective —
minimizing the number of scheduled jobs — maps to minimizing dispatches here,
and re-compiles are the analogue of job setup cost.

Device-resident phase pipeline (DESIGN.md §4): a job can be dispatched

* **fused** — the ``count >= min_count`` filter runs on device inside the
  shard_map'd job, so only a bit-packed keep mask (``C/8`` bytes) plus the
  min-count-filtered int32 counts cross back to the host instead of every
  padded candidate's count;
* **async** — ``phase_count_async`` returns a :class:`CountFuture` and never
  calls ``block_until_ready``; the host keeps generating the next level's
  candidates while the job is in flight (``RuntimeStats.overlap_seconds``
  records that overlap).

Cluster-scale meshes (DESIGN.md §11): the runtime accepts a true 2-D
``(data, cand)`` mesh — transaction shards along ``data`` *and* candidate
shards along ``cand`` — counted by the same single shard_map job: each
device counts its candidate shard against its transaction shard, ``psum``
reduces over ``data`` only, and the results stay sharded over ``cand``
(the per-shard keep masks are packed to exact word boundaries so they
concatenate into one global bitstream).  :meth:`MapReduceRuntime.repartition`
rebuilds the mesh as a different ``(n_data, n_cand)`` split of the same
devices between levels and re-scatters the retained database — the elastic
re-layout the per-level cost-model decision drives — and
:meth:`MapReduceRuntime.rescatter` re-places shards from the host copy (the
shard-recovery half of the fault-tolerant retry protocol).
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.kernels.autotune import defaults_fit, tuned_blocks
from repro.obs.metrics import get_registry

from .counting import local_counts, local_counts_vertical
from .bitset import WORD_BITS, floor_log2

IMPLS = ("jnp", "matmul", "pallas", "pallas_interpret",
         "matmul_pallas", "matmul_pallas_interpret",
         "vertical", "vertical_matmul",
         "vertical_pallas", "vertical_pallas_interpret",
         "vertical_matmul_pallas", "vertical_matmul_pallas_interpret")


@dataclasses.dataclass
class RuntimeStats:
    dispatches: int = 0
    compiles: int = 0
    rows_counted: int = 0       # candidates counted across all dispatches
    fused_dispatches: int = 0   # jobs that filtered on device
    overlap_seconds: float = 0.0  # host gen time spent while a job was in flight
    bytes_to_host: int = 0      # result bytes actually fetched from device
    bytes_to_device: int = 0    # database and candidate bytes, per device copy
    exchange_bytes: int = 0     # count bytes all devices put into the psums
    repartitions: int = 0       # elastic mesh re-layouts (DESIGN.md §11)
    scatter_seconds: float = 0.0  # host time spent (re-)placing the database
    pack_seconds: float = 0.0   # of it, building the vertical per-shard bitmaps
    index_seconds: float = 0.0  # building the vertical forms' item index

    def __setattr__(self, name, value):
        # Mirror every increment into the process-wide metrics registry
        # (DESIGN.md §13) so `--metrics-out` snapshots see runtime counters
        # without touching the `stats.x += n` call sites.  Positive deltas
        # only: per-runtime stats reset, the registry accumulates.
        prev = getattr(self, name, None)
        if prev is not None:
            delta = value - prev
            if delta > 0:
                get_registry().counter(f"mine.{name}").inc(delta)
        object.__setattr__(self, name, value)


def _pack_mask(keep: jax.Array) -> jax.Array:
    """(n,) bool → (ceil(n/32),) uint32, bit ``i%32`` of word ``i//32`` = keep[i]."""
    pad = (-keep.shape[0]) % 32
    if pad:
        keep = jnp.concatenate([keep, jnp.zeros((pad,), keep.dtype)])
    b = keep.reshape(-1, 32).astype(jnp.uint32)
    return (b << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        axis=1, dtype=jnp.uint32)


def _unpack_mask(packed: np.ndarray, n: int) -> np.ndarray:
    """Inverse of :func:`_pack_mask` on host → (n,) bool."""
    bits = np.unpackbits(packed.view(np.uint8), bitorder="little")
    return bits[:n].astype(bool)


class CountFuture:
    """Handle for one in-flight counting job.

    The device arrays are not fetched (and the host never blocks) until
    ``result()`` is called — the double-buffering half of the async pipeline.

    ``result()`` returns host counts ``(C,) int64`` for a plain job, or a
    ``(keep_mask (C,) bool, counts (C,) int64)`` pair for a fused job (counts
    are zeroed where the device filter dropped the candidate; ``None`` when
    the job was dispatched with ``with_counts=False``).
    """

    def __init__(self, runtime: "MapReduceRuntime", raw, *, fused: bool,
                 with_counts: bool, n_rows: int):
        self._rt = runtime
        self._raw = raw
        self._fused = fused
        self._with_counts = with_counts
        self._n = n_rows
        self._result = None

    def ready(self) -> bool:
        """Non-blocking completion probe."""
        return all(leaf.is_ready()
                   for leaf in jax.tree_util.tree_leaves(self._raw))

    def result(self):
        if self._result is None:
            raw = jax.block_until_ready(self._raw)
            stats = self._rt.stats
            if self._fused:
                packed = np.asarray(raw[0])
                stats.bytes_to_host += packed.nbytes
                # always a bit-packed uint32 stream: candidate-sharded jobs
                # pack per shard at exact word boundaries (rows padded to a
                # multiple of 32·n_cand_shards), so the shard concatenation
                # is the global bitstream
                keep = _unpack_mask(packed, self._n)
                counts = None
                if self._with_counts:
                    c = np.asarray(raw[1])
                    stats.bytes_to_host += c.nbytes
                    counts = c[:self._n].astype(np.int64)
                self._result = (keep, counts)
            else:
                c = np.asarray(raw)
                stats.bytes_to_host += c.nbytes
                self._result = c[:self._n].astype(np.int64)
        return self._result


class MapReduceRuntime:
    """Support-counting runtime over a 1-D data mesh or a 2-D (data, cand) mesh.

    Args:
      mesh: a Mesh containing a ``data`` axis (other axes are unused here but
        allowed, so the production (data, model) mesh can be passed directly).
        Defaults to a 1-D mesh over all local devices; pass
        ``launch.mesh.make_mining_mesh(n_data, n_cand)`` for the 2-D
        transaction×candidate decomposition (DESIGN.md §11).
      impl: counting implementation — any of ``IMPLS`` (popcount families
        "jnp"/"pallas"/"vertical*" plus their bit-plane "matmul" twins,
        DESIGN.md §10), or None/"auto": the cross-family autotune plan
        winner for the database's *per-shard* shape bucket, resolved at
        :meth:`scatter_db` time (static fallback when autotune is off or
        the plan is unavailable: "pallas" on TPU, "vertical" elsewhere).
      cand_axis: optional mesh axis name to additionally shard *candidates*
        over (2-D decomposition; beyond-paper, see DESIGN.md §11). None
        replicates candidates, matching the paper (every mapper holds the
        full trie).
      autotune: consult the block-size autotuner when building counting jobs
        (kernels/autotune.py); False pins the static defaults.
    """

    def __init__(self, mesh: Mesh | None = None, impl: str | None = None,
                 cand_axis: str | None = None, autotune: bool = True):
        if mesh is None:
            mesh = make_mesh((len(jax.devices()),), ("data",))
        self._auto_impl = impl is None or impl == "auto"
        if self._auto_impl:
            # static fallback until scatter_db sees the data shape and can
            # consult the cross-family plan — TPU: dense horizontal Pallas
            # kernel; CPU: vertical layout (§Perf iteration M-D)
            impl = "pallas" if jax.default_backend() == "tpu" else "vertical"
        if impl not in IMPLS:
            raise ValueError(f"unknown impl {impl!r}; options: {IMPLS}")
        if cand_axis is not None and cand_axis not in mesh.shape:
            raise ValueError(f"cand_axis {cand_axis!r} not in mesh axes "
                             f"{tuple(mesh.shape)}")
        self.mesh = mesh
        self.impl = impl
        self.cand_axis = cand_axis
        self.autotune = autotune
        self.stats = RuntimeStats()
        self._shape_cache: set = set()
        self._jitted = {}
        self._n_items: int | None = None
        self._db_masks: np.ndarray | None = None  # host copy for re-scatter

    @property
    def n_data_shards(self) -> int:
        return self.mesh.shape["data"]

    @property
    def n_cand_shards(self) -> int:
        return self.mesh.shape[self.cand_axis] if self.cand_axis else 1

    @property
    def mesh_split(self) -> tuple[int, int]:
        """(n_data, n_cand) — the current transaction×candidate split."""
        return (self.n_data_shards, self.n_cand_shards)

    @property
    def vertical(self) -> bool:
        return self.impl.startswith("vertical")

    @property
    def can_repartition(self) -> bool:
        """True when the mesh is runtime-owned (only data/cand-style axes)
        and a database has been scattered, so :meth:`repartition` can
        rebuild the split from the retained host copy."""
        return (self._db_masks is not None
                and set(self.mesh.axis_names) <= {"data", "cand", "model"})

    # -- data distribution ---------------------------------------------------

    def scatter_db(self, db_masks: np.ndarray, n_items: int | None = None):
        """Zero-pad rows to the shard multiple and place shards on devices.

        Horizontal impls return the (N, W) row-sharded matrix; the vertical
        impl returns (d, I+1, Tw) per-shard item-major bitmaps (built host-side
        once — the InputFormat step of the job).  The unpadded host copy is
        retained for :meth:`repartition`/:meth:`rescatter`."""
        self._db_masks = np.asarray(db_masks, dtype=np.uint32)
        if n_items is not None:
            self._n_items = n_items
        return self._scatter_current()

    def _scatter_current(self):
        """(Re-)place the retained database on the current mesh."""
        from .bitset import vertical_pack
        db_masks = self._db_masks
        n, w = db_masks.shape
        t0 = time.perf_counter()
        if self._auto_impl and self.autotune and self._n_items is not None:
            # cross-family plan winner at a representative *per-shard* phase
            # shape — each device counts C/n_cand candidates against
            # T/n_data transactions, so the plan must bucket on the extents
            # a shard actually sees, not the global ones (DESIGN.md §11);
            # counts are bit-exact across impls, so the mining result is
            # identical whichever family wins
            from repro.kernels.autotune import tuned_plan
            rep_c = min(max(16 * self._n_items, 256), 4096)
            plan = tuned_plan("count", C=max(rep_c // self.n_cand_shards, 32),
                              T=max(n // self.n_data_shards, 1), W=w, kmax=4)
            if plan is not None and plan["impl"] in IMPLS:
                self.impl = plan["impl"]
        elif (self._auto_impl and self.impl == "pallas"
              and not defaults_fit("pallas", w)):
            self.impl = "jnp"     # the static TPU fallback, too wide for VMEM
        d = self.n_data_shards
        pad = (-n) % d
        if pad:
            db_masks = np.concatenate(
                [db_masks, np.zeros((pad, w), np.uint32)], axis=0)
        if self.vertical:
            assert self._n_items is not None, "vertical impl needs n_items"
            per = db_masks.shape[0] // d
            t_pack = time.perf_counter()
            host = np.stack([
                vertical_pack(db_masks[i * per:(i + 1) * per], self._n_items)
                for i in range(d)])                      # (d, I+1, Tw)
            self.stats.pack_seconds += time.perf_counter() - t_pack
            spec = P("data", None, None)
        else:
            host, spec = db_masks, P("data", None)
        out = jax.device_put(host, NamedSharding(self.mesh, spec))
        # sharded over data, so every other mesh axis holds a copy
        self.stats.bytes_to_device += host.nbytes * (self.mesh.size // d)
        self.stats.scatter_seconds += time.perf_counter() - t0
        return out

    def rescatter(self):
        """Re-place shards from the host copy on the *same* mesh — the
        recovery step of the per-phase retry protocol (a failed shard's
        state is rebuilt from the retained database, the analogue of HDFS
        re-reading an input split on task re-execution)."""
        if self._db_masks is None:
            raise RuntimeError("rescatter() requires a prior scatter_db()")
        return self._scatter_current()

    def repartition(self, n_data: int, n_cand: int = 1):
        """Elastically re-layout as an ``(n_data, n_cand)`` split of the same
        devices and re-scatter the retained database (DESIGN.md §11).

        Candidate counts explode between Apriori levels (k=2→3 especially),
        so the best split is per-level, not per-run: the cost-model
        controller prices the next phase's (C, T) extents and calls this
        between levels.  Compiled jobs are cached per (mesh, shape) key, so
        returning to a previously used split never re-compiles.

        Returns the new sharded database handle.
        """
        if not self.can_repartition:
            raise RuntimeError(
                "repartition() needs a scatter_db'd database and a "
                "runtime-owned mesh (axes within data/cand/model)")
        n_dev = self.mesh.size
        if n_data * n_cand != n_dev:
            raise ValueError(f"split {n_data}x{n_cand} != {n_dev} devices")
        if (n_data, n_cand) != self.mesh_split:
            self.mesh = make_mesh((n_data, n_cand), ("data", "cand"))
            self.cand_axis = "cand" if n_cand > 1 else None
            self.stats.repartitions += 1
        return self._scatter_current()

    # -- one MapReduce job ----------------------------------------------------

    def _tuned(self, payload_shape, db_shape) -> dict:
        """Autotuned block sizes for one counting job (static at trace time).

        Tuning keys bucket on *per-shard* extents — C/n_cand candidate rows
        against this device's transaction shard — because that is the shape
        the kernel actually runs at (DESIGN.md §11); the vertical db_shape is
        already per-shard ((d, I+1, Tw_shard))."""
        from repro.kernels.autotune import DEFAULTS
        dc = self.n_cand_shards
        if self.vertical:
            kind = self.impl[len("vertical"):].lstrip("_") or "jnp"
            impl_key = "vertical" if kind == "jnp" else f"vertical_{kind}"
            if not self.autotune:
                return dict(DEFAULTS[impl_key])
            C, kmax = payload_shape
            # W is the mask width ceil(I/32), as in the plan that chose the
            # family, so both price the same (I+1)-row blocks
            return tuned_blocks(impl_key, C=max(C // dc, 1), T=db_shape[-1],
                                W=max(-(-(db_shape[-2] - 1) // 32), 1),
                                kmax=kmax)
        if not self.autotune:
            return dict(DEFAULTS[self.impl])
        C, W = payload_shape
        return tuned_blocks(self.impl, C=max(C // dc, 1),
                            T=max(db_shape[0] // self.n_data_shards, 1), W=W)

    def _build(self, fused: bool, with_counts: bool, payload_shape, db_shape,
               n_valid: int | None = None):
        impl = self.impl
        vertical = self.vertical
        cand_axis = self.cand_axis
        mesh = self.mesh
        cand_spec = P(cand_axis, None) if cand_axis else P(None, None)
        out_spec = P(cand_axis) if cand_axis else P()
        blocks = self._tuned(payload_shape, db_shape)

        if vertical:
            kind = impl[len("vertical"):].lstrip("_") or "jnp"

            def count_local(vdb_local, idx_local):
                return local_counts_vertical(vdb_local[0], idx_local,
                                             impl=kind, **blocks)
            db_spec = P("data", None, None)
        else:
            def count_local(db_local, cands_local):
                return local_counts(db_local, cands_local, impl, **blocks)
            db_spec = P("data", None)

        if fused:
            def mapper(db_local, payload_local, thr):
                local = count_local(db_local, payload_local)  # map + combine
                counts = jax.lax.psum(local, "data")          # reduce
                if cand_axis:
                    # shard-symmetric n_valid: every shard keeps its full
                    # (identical) row extent — rows padded to 32·n_cand —
                    # and masks validity from its global row offset, so the
                    # per-shard bit-packed masks land on exact word
                    # boundaries and concatenate into the global bitstream
                    keep = counts >= thr                      # filter, fused
                    if n_valid is not None:
                        per = counts.shape[0]
                        base = jax.lax.axis_index(cand_axis) * per
                        valid = base + jnp.arange(per, dtype=jnp.int32) < n_valid
                        keep = keep & valid
                else:
                    if n_valid is not None:
                        counts = counts[:n_valid]  # pad tail never leaves
                    keep = counts >= thr                      # filter, fused
                mask = _pack_mask(keep)
                if with_counts:
                    return mask, jnp.where(keep, counts, 0)
                return (mask,)
            in_specs = (db_spec, cand_spec, P())
            pack_spec = P(cand_axis) if cand_axis else P()
            out_specs = (pack_spec, out_spec) if with_counts else (pack_spec,)
        else:
            def mapper(db_local, payload_local):
                local = count_local(db_local, payload_local)  # map + combine
                return jax.lax.psum(local, "data")            # reduce
            in_specs = (db_spec, cand_spec)
            out_specs = out_spec

        # a stable program name per form and mode, e.g.
        # jit_mapper_vertical_pallas_plain in a device trace
        mode = ("fused" if with_counts else "fused_mask") if fused else "plain"
        mapper.__name__ = mapper.__qualname__ = f"mapper_{impl}_{mode}"
        fn = jax.shard_map(mapper, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn)

    def _padded_indices(self, masks: np.ndarray) -> np.ndarray:
        """(C, W) masks (zero rows allowed) → (C, kmax) item ids, ascending in
        each row and padded with the valid-mask sentinel row (AND identity);
        ``kmax`` is the largest popcount, at least 1.

        Word by word: the nonzero words in row-major order, then their set
        bits peeled lowest first (``w & -w``), so no array larger than
        ``(C, W)`` is built."""
        C, W = masks.shape
        flat = np.flatnonzero(masks)
        rows, words = np.divmod(flat, W)
        w = masks.reshape(-1)[flat]
        n_bits = np.bitwise_count(w).astype(np.int64)
        # a word's first column: the set bits of its row's earlier words
        before = np.cumsum(n_bits) - n_bits
        first = np.ones(rows.size, bool)
        first[1:] = rows[1:] != rows[:-1]
        col = before - np.maximum.accumulate(np.where(first, before, 0))
        kmax = max(int((col + n_bits).max()) if rows.size else 1, 1)
        idx = np.full((C, kmax), self._n_items, np.int32)
        item0 = words * WORD_BITS
        while w.size:
            bit = w & -w
            idx[rows, col] = item0 + floor_log2(bit)
            w ^= bit
            col += 1
            live = w != 0
            rows, col, w, item0 = rows[live], col[live], w[live], item0[live]
        return idx

    def place_candidates(self, cands_padded: np.ndarray) -> jax.Array:
        """Build one job's candidate payload on the host and place it on the
        mesh: the ``(C, kmax)`` item index for the vertical forms, the
        ``(C, W)`` masks otherwise.

        ``cands_padded`` rows must already be padded to the runtime block
        multiple (see phases.bucket_pad); a candidate-sharded mesh pads them
        further here."""
        if self.cand_axis is not None:
            # candidate-sharded jobs need rows divisible by the cand shards
            # AND per-shard rows on a 32-row word boundary, so the fused
            # per-shard keep masks bit-pack without intra-shard padding
            mult = 32 * self.n_cand_shards
            pad = (-cands_padded.shape[0]) % mult
            if pad:
                cands_padded = np.concatenate(
                    [cands_padded,
                     np.zeros((pad, cands_padded.shape[1]), np.uint32)])
        if self.vertical:
            t_index = time.perf_counter()
            host = self._padded_indices(cands_padded)
            self.stats.index_seconds += time.perf_counter() - t_index
        else:
            host = np.asarray(cands_padded, dtype=np.uint32)
        spec = P(self.cand_axis, None) if self.cand_axis else P(None, None)
        payload = jax.device_put(host, NamedSharding(self.mesh, spec))
        # each candidate shard goes to every device of the other mesh axes
        self.stats.bytes_to_device += host.nbytes * (
            self.mesh.size // self.n_cand_shards)
        return payload

    def exchange_bytes(self, payload: jax.Array) -> int:
        """Bytes of the int32 counts that all devices put into one job's
        ``psum`` over ``data``: each device's rows of ``payload`` times 4 B,
        over the whole mesh; 0 on a single data shard, where nothing is
        exchanged."""
        if self.n_data_shards == 1:
            return 0
        rows = int(payload.shape[0]) // self.n_cand_shards
        return rows * 4 * self.mesh.size

    def dispatch_count(self, db_sharded, payload: jax.Array,
                       min_count: float | None = None,
                       with_counts: bool = True,
                       n_valid: int | None = None) -> CountFuture:
        """Dispatch one MapReduce job over a placed payload
        (:meth:`place_candidates`) without waiting for it.

        When ``min_count`` is given the job is **fused**: the support filter
        runs on device and only the packed keep mask (+ filtered counts
        unless ``with_counts=False``) is transferred when the returned
        :class:`CountFuture` is consumed — sliced on device to ``n_valid``
        rows (the real, pre-padding candidate count), so the bucket-pad tail
        never crosses to the host.
        """
        fused = min_count is not None
        if not fused:
            # unfused keeps the legacy full-padded transfer
            n_valid = None
        n_rows = int(payload.shape[0]) if n_valid is None else int(n_valid)
        key = (fused, with_counts, n_valid, db_sharded.shape, payload.shape,
               tuple(self.mesh.shape.items()), self.cand_axis, self.impl)
        if key not in self._jitted:
            self._jitted[key] = self._build(fused, with_counts,
                                            payload.shape, db_sharded.shape,
                                            n_valid=n_valid)
        if key not in self._shape_cache:
            self._shape_cache.add(key)
            self.stats.compiles += 1
        args = (db_sharded, payload)
        if fused:
            # integer threshold: counts are ints, so >= ceil(min_count) is
            # exactly the host-side `counts >= min_count` float comparison
            args += (jnp.int32(math.ceil(min_count)),)
        out = self._jitted[key](*args)
        self.stats.dispatches += 1
        self.stats.rows_counted += int(payload.shape[0])
        self.stats.exchange_bytes += self.exchange_bytes(payload)
        if fused:
            self.stats.fused_dispatches += 1
        return CountFuture(self, out, fused=fused, with_counts=with_counts,
                           n_rows=n_rows)

    def phase_count_async(self, db_sharded, cands_padded: np.ndarray,
                          min_count: float | None = None,
                          with_counts: bool = True,
                          n_valid: int | None = None) -> CountFuture:
        """:meth:`place_candidates` then :meth:`dispatch_count`."""
        return self.dispatch_count(db_sharded,
                                   self.place_candidates(cands_padded),
                                   min_count=min_count,
                                   with_counts=with_counts, n_valid=n_valid)

    def phase_count(self, db_sharded, cands_padded: np.ndarray) -> np.ndarray:
        """Synchronous unfused job: host int64 counts for every padded row."""
        return self.phase_count_async(db_sharded, cands_padded).result()

    def phase_count_filtered(self, db_sharded, cands_padded: np.ndarray,
                             min_count: float, with_counts: bool = True,
                             n_valid: int | None = None):
        """Synchronous fused job → ``(keep_mask, filtered_counts_or_None)``."""
        return self.phase_count_async(db_sharded, cands_padded,
                                      min_count=min_count,
                                      with_counts=with_counts,
                                      n_valid=n_valid).result()
