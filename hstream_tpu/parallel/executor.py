"""Host executor for mesh-sharded queries.

Same host-side semantics as engine.QueryExecutor (watermark, window
bookkeeping, key dictionary, emission); only the device callables differ —
they come from a ShardedLattice, so every process() scatters a sharded
batch into per-chip partial lattices and drains merge over the mesh.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from hstream_tpu.engine import lattice as se_lattice
from hstream_tpu.engine.executor import QueryExecutor, StagedBatch
from hstream_tpu.engine.plan import AggregateNode
from hstream_tpu.engine.types import Schema
from hstream_tpu.parallel.lattice import ShardedLattice


class ShardedQueryExecutor(QueryExecutor):
    """QueryExecutor whose lattice lives sharded over a device mesh.

    ``initial_keys`` is the fixed GLOBAL key capacity (must divide by the
    key-axis size). Key growth re-shards through the host — rare and
    logged; size capacity generously for production queries.
    """

    # the sharded drain path fetches synchronously (one transfer of
    # the per-shard stack); the deferral flag would be a silent no-op
    supports_deferred_changes = False
    # no keyed peek: a key's cells are partials spread over the data
    # shards of one key shard; a pull that pins its key takes the whole
    # peek and the read plane's filter (server/views.py)
    peek_key = None

    def __init__(self, node: AggregateNode, schema: Schema, *, mesh,
                 data_axis: str = "data", key_axis: str = "key",
                 emit_changes: bool = True, initial_keys: int = 1024,
                 batch_capacity: int = 4096):
        self._mesh = mesh
        self._data_axis = data_axis
        self._key_axis = key_axis
        # device dispatches that ran under shard_map (step + drain);
        # the query task mirrors deltas into the sharded_dispatches
        # stat family
        self.sharded_dispatches = 0
        super().__init__(node, schema, emit_changes=emit_changes,
                         initial_keys=initial_keys,
                         batch_capacity=batch_capacity)

    def _compile(self) -> None:
        from hstream_tpu.engine.expr import columns_of

        self._layout = tuple(
            (name, se_lattice.layout_tag(self.schema.type_of(name)))
            for name in self._needed_cols)
        sharded = ShardedLattice(
            self.spec, self.schema, self._filter_expr,
            self.batch_capacity * self.spec.windows_per_record,
            self._mesh, self._layout, data_axis=self._data_axis,
            key_axis=self._key_axis)
        self._sharded = sharded
        self._step = sharded.step
        self._extract_slot = self._count_close_kernel(sharded.extract_slot)
        self._reset_slot = self._count_close_kernel(sharded.reset_slot)
        self._extract_reset_slots = self._count_close_kernel(
            sharded.extract_reset_slots)
        self._extract_slots = sharded.extract_slots  # peek: read path
        self._reset_slots = self._count_close_kernel(sharded.reset_slots)
        self._extract_touched = sharded.extract_touched
        self._null_specs = [
            (key, sorted(columns_of(agg.input)))
            for key, agg in zip(sharded.null_keys, self.spec.aggs)
            if key is not None
        ]
        # Replace single-chip state from the base __init__ with sharded
        # state (keyed planes gain a leading data-shard axis); the grow
        # path installs its own padded arrays instead.
        if not getattr(self, "_defer_state_init", False):
            cur = getattr(self, "state", None)
            if cur is None or cur["count"].ndim == 2:
                self.state = sharded.init_state()

    def _grow_keys(self) -> None:
        # gather → pad global key axis (axis 1 of keyed planes) → re-shard
        import jax

        new_k = self.spec.n_keys * 2
        self._grow_key_arrays(new_k)
        kinds = se_lattice.plane_merge_kinds(self.spec)
        extra = new_k - self.spec.n_keys
        # key growth re-shards through the host: one fetch per plane is
        # unavoidable (mixed dtypes/ranks cannot stack).
        # analyze: ok dispatch-sync — rare re-shard path by design
        host = {k: np.asarray(v) for k, v in self.state.items()}
        grown = {}
        for k, v in host.items():
            if k == "slot_start":
                grown[k] = v
                continue
            pad = [(0, 0), (0, extra)] + [(0, 0)] * (v.ndim - 2)
            fill = (np.inf if kinds.get(k) == "min"
                    else -np.inf if kinds.get(k) == "max" and
                    v.dtype == np.float32 else 0)
            grown[k] = np.pad(v, pad, constant_values=fill)
        self.spec = se_lattice.LatticeSpec(
            n_keys=new_k, window=self.spec.window, aggs=self.spec.aggs,
            hll=self.spec.hll, qcfg=self.spec.qcfg,
            track_touched=self.spec.track_touched)
        self._defer_state_init = True
        try:
            self._compile()
        finally:
            self._defer_state_init = False
        self.state = {
            k: jax.device_put(v, self._sharded.state_sharding(k))
            for k, v in grown.items()
        }

    def stage_columnar(self, key_ids, ts_ms, cols, nulls=None,
                       upload: bool = True) -> StagedBatch | None:
        # Sharded execution keeps the v1 packed transport (the batch is
        # distributed by shard_map, not the link codec), so staging
        # degrades to a host-held batch; process_staged routes combo=None
        # through the synchronous sharded path. IngestPipeline therefore
        # still works, just without encode/step overlap.
        key_ids = np.asarray(key_ids, dtype=np.int32)
        if len(key_ids) == 0:
            return None
        ts = np.asarray(ts_ms, dtype=np.int64)
        return StagedBatch(
            n=len(key_ids), cap=0, combo=None, bases=None, words=None,
            epoch=0, ts_min=int(ts.min()), ts_max=int(ts.max()),
            key_ids=key_ids, ts_ms=ts, cols=cols, nulls=nulls)

    # contract: dispatches<=1 fetches<=0
    def _run_step(self, cap, n, key_ids, ts_rel, cols, valid,
                  null_streams, wm_rel) -> None:
        # The sharded path keeps the v1 packed transport: the batch is
        # split across the data axis by shard_map, so the wire format is
        # the intra-host one (device_put with a sharding), not the
        # bit-packed link codec.
        null_masks = [null_streams.get(nk) for nk, _ in self._null_specs]
        packed = se_lattice.pack_batch_host(
            cap, n, key_ids,
            # both callers narrow ts_rel only after their own span check
            # analyze: ok overflow-narrowing — caller-guarded narrow
            np.asarray(ts_rel, dtype=np.int32), valid,
            cols, null_masks, self._layout)
        self.state = self._step(self.state, wm_rel, packed)
        self.sharded_dispatches += 1

    # contract: dispatches<=1 fetches<=1
    def _drain_changes(self):
        """Columnar sharded changelog drain: ONE host fetch of the
        per-key-shard packed buffers, then the same batched decode the
        single-chip path uses (kid rows already carry GLOBAL key ids).
        A lone shard's batch stays a ColumnarEmit."""
        from hstream_tpu.common.columnar import extend_rows

        self.state, packed = self._extract_touched(self.state)
        self.sharded_dispatches += 1
        packed = np.asarray(packed)        # [n_key_shards, rows, max_out]
        out = None
        for s in range(self._sharded.n_key):
            out = extend_rows(out, self._decode_changes(packed[s],
                                                        self.epoch))
        return out if out is not None else []
