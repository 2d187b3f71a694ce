"""Multi-chip sharding of the window-state lattice.

The reference is single-process for compute — its only cross-host axes are
storage replication and round-robin consumer dispatch (SURVEY §2.3;
hstream/src/HStream/Server/Handler.hs:896-922). The TPU-native design
scales the aggregation hot path itself over a 2-D device mesh:

  * ``data`` axis — records of each micro-batch are sharded across chips;
    every chip scatters its shard into a **partial lattice**. Because all
    accumulator planes are commutative monoids (lattice.plane_merge_kinds),
    partials merge exactly at drain points.
  * ``key`` axis — the key dimension of every plane is sharded, bounding
    per-chip HBM. Records are broadcast along ``key`` (the batch in_spec
    only names the data axis) and each chip masks the scatter to the key
    range it owns — no all-to-all in the hot path; the scatter itself does
    the routing.

State arrays carry a leading device axis of length ``D`` (the data-axis
size): a keyed plane is ``[D, K, W, ...]`` sharded
``P(data, key)``. The hot step runs under ``jax.shard_map`` with **zero
collectives**; merges (psum / pmin / pmax over ``data``, all riding ICI)
happen only when the host drains state — window close, changelog pull,
view peek — amortized over the window length.

This mirrors the scaling-book recipe: pick a mesh, annotate shardings, let
the compiled collectives ride ICI. DCN never sees lattice traffic; it is
reserved for the log-store replication plane (hstream_tpu.store).

The shard_map hygiene here (collectives only inside mesh bodies, no
host callbacks/fetches in them, axis names spelled consistently) is
checked by the tools/analyze shardmap pass, and the kernels run for
real in CI on a virtual 8-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8); the static pass
still catches the classes — per-shard host syncs, axis typos — that
only real ICI latency or multi-host meshes would trip.
"""

from __future__ import annotations

import functools
from typing import Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hstream_tpu.engine import lattice
from hstream_tpu.engine.lattice import (
    EMPTY_START,
    LatticeSpec,
    build_step_fn,
    compile_agg_inputs,
    finalize_column,
    init_value,
    plane_merge_kinds,
)

_MERGE = {
    "sum": jax.lax.psum,
    "min": jax.lax.pmin,
    "max": jax.lax.pmax,
}


def _keyed(name: str) -> bool:
    return name != "slot_start"


class ShardedLattice:
    """The lattice of one query, sharded over a (data, key) mesh.

    Drop-in provider of the CompiledLattice callables with identical
    signatures (state first, host scalars as np types), so the host
    executor drives single-chip and multi-chip lattices the same way.
    ``n_keys`` of ``spec`` is the GLOBAL key capacity; it must divide by
    the key-axis size.
    """

    def __init__(self, spec: LatticeSpec, schema, filter_expr,
                 max_out: int, mesh: Mesh, layout,
                 data_axis: str = "data", key_axis: str = "key"):
        from hstream_tpu.engine.expr import compile_device

        self.layout = layout
        self.mesh = mesh
        self.data_axis = data_axis
        self.key_axis = key_axis if key_axis in mesh.axis_names else None
        self.n_data = mesh.shape[data_axis]
        self.n_key = mesh.shape[self.key_axis] if self.key_axis else 1
        if spec.n_keys % self.n_key != 0:
            raise ValueError(
                f"global key capacity {spec.n_keys} not divisible by "
                f"key-axis size {self.n_key}")
        self.spec = spec
        self.local_spec = LatticeSpec(
            n_keys=spec.n_keys // self.n_key, window=spec.window,
            aggs=spec.aggs, hll=spec.hll, qcfg=spec.qcfg,
            track_touched=spec.track_touched)
        self.max_out = max_out

        agg_inputs, self.null_keys = compile_agg_inputs(spec, schema)
        filter_fn = (compile_device(filter_expr, schema)
                     if filter_expr is not None else None)
        self._local_step = build_step_fn(self.local_spec, agg_inputs,
                                         filter_fn)
        self._merge_kinds = plane_merge_kinds(spec)
        bad = sorted(k for k, v in self._merge_kinds.items()
                     if v not in _MERGE)
        if bad:
            raise ValueError(
                f"plane(s) {bad} have no elementwise merge (TOPK): "
                "sharded execution is not supported for this query")
        self._state_specs = None  # built lazily from init_state's tree
        self._build()

    # ---- sharding specs ----------------------------------------------------

    def state_spec(self, name: str) -> P:
        if _keyed(name):
            return P(self.data_axis, self.key_axis)
        return P(self.data_axis)

    def state_sharding(self, name: str) -> NamedSharding:
        return NamedSharding(self.mesh, self.state_spec(name))

    def init_state(self) -> dict[str, jnp.ndarray]:
        """Global sharded state: local init replicated along ``data`` (all
        init values are merge identities, so D partial copies are exact)."""
        local = lattice.init_state(self.spec)  # global K, host-side
        out = {}
        for name, v in local.items():
            g = jnp.broadcast_to(v[None], (self.n_data,) + v.shape)
            out[name] = jax.device_put(g, self.state_sharding(name))
        return out

    def _specs_of(self, state_tree: Mapping[str, jnp.ndarray]):
        return {k: self.state_spec(k) for k in state_tree}

    # ---- compiled callables ------------------------------------------------

    def _build(self) -> None:
        mesh = self.mesh
        data_axis, key_axis = self.data_axis, self.key_axis
        Kl = self.local_spec.n_keys
        merge = self._merge_kinds
        spec_tree = {k: self.state_spec(k)
                     for k in lattice.init_state(self.spec)}
        local_spec = self.local_spec

        def key_offset():
            if key_axis is None:
                return 0
            return jax.lax.axis_index(key_axis) * Kl

        layout, null_keys = self.layout, self.null_keys

        def step_local(state, watermark, packed):
            local = {k: v[0] for k, v in state.items()}
            key_ids, ts, valid, cols = lattice.unpack_batch_device(
                packed, layout, null_keys)
            kid = key_ids - key_offset()
            ok = valid & (kid >= 0) & (kid < Kl)
            # slot_valid = the pre-key-ownership mask: slot_start is
            # key-independent, so every key shard must update it from ALL
            # valid records for the replicated out-spec to hold.
            new = self._local_step(local, watermark, kid, ts, ok, cols,
                                   slot_valid=valid)
            return {k: v[None] for k, v in new.items()}

        # packed batch [rows, B]: rows replicated, records sharded on data
        self.step = jax.jit(jax.shard_map(
            step_local, mesh=mesh,
            in_specs=(spec_tree, P(), P(None, data_axis)),
            out_specs=spec_tree, check_vma=False))

        def merged_col(state, slot):
            """One slot column, merged over the data axis -> {plane: [Kl]}"""
            col = {}
            for k, v in state.items():
                if k in ("slot_start", "touched"):
                    continue
                col[k] = _MERGE[merge[k]](v[0, :, slot], data_axis)
            return col

        def extract_local(state, slot):
            col = merged_col(state, slot)
            outs = finalize_column(local_spec, col)
            ws = jax.lax.pmax(state["slot_start"][0, slot], data_axis)
            return lattice.pack_extract_rows(local_spec, col["count"],
                                             ws, outs)

        # packed [2+n_aggs, K] — key axis concatenated over shards
        self.extract_slot = jax.jit(jax.shard_map(
            extract_local, mesh=mesh,
            in_specs=(spec_tree, P()),
            out_specs=P(None, key_axis), check_vma=False))

        def reset_local(state, slot):
            out = dict(state)
            for i, agg in enumerate(local_spec.aggs):
                if agg.kind == lattice.AggKind.COUNT_ALL:
                    continue  # aliases `count`, reset below
                name = lattice._plane_name(i, agg)
                out[name] = state[name].at[:, :, slot].set(init_value(agg))
                if agg.kind == lattice.AggKind.AVG:
                    out[name + "_n"] = state[name + "_n"].at[
                        :, :, slot].set(0)
            out["count"] = state["count"].at[:, :, slot].set(0)
            out["touched"] = state["touched"].at[:, :, slot].set(False)
            out["slot_start"] = state["slot_start"].at[:, slot].set(
                EMPTY_START)
            return out

        self.reset_slot = jax.jit(jax.shard_map(
            reset_local, mesh=mesh,
            in_specs=(spec_tree, P()),
            out_specs=spec_tree, check_vma=False))

        # ---- fused multi-slot close (one dispatch per close cycle) ----
        # Same contract as lattice.build_extract_reset_slots, with the
        # monoid merge riding ICI (psum/pmin/pmax over `data`) BEFORE
        # the single host fetch: slots i32[P] (entries < 0 pad), packed
        # out [P, 2+rows, K] with the key axis concatenated over shards
        # so kid indices in the buffer are GLOBAL key ids.

        def _extract_slots_local(state, slots):
            valid = slots >= 0
            safe = jnp.where(valid, slots, 0)

            def one(slot):
                col = merged_col(state, slot)
                outs = finalize_column(local_spec, col)
                ws = jax.lax.pmax(state["slot_start"][0, slot], data_axis)
                return lattice.pack_extract_rows(local_spec,
                                                 col["count"], ws, outs)

            packed = jax.vmap(one)(safe)
            return jnp.where(valid[:, None, None], packed, 0)

        def _reset_slots_local(state, slots):
            rs = jnp.where(slots >= 0, slots, local_spec.n_slots)
            out = dict(state)
            for i, agg in enumerate(local_spec.aggs):
                if agg.kind == lattice.AggKind.COUNT_ALL:
                    continue  # aliases `count`, reset below
                name = lattice._plane_name(i, agg)
                out[name] = state[name].at[:, :, rs].set(
                    init_value(agg), mode="drop")
                if agg.kind == lattice.AggKind.AVG:
                    out[name + "_n"] = state[name + "_n"].at[
                        :, :, rs].set(0, mode="drop")
            out["count"] = state["count"].at[:, :, rs].set(0, mode="drop")
            out["touched"] = state["touched"].at[:, :, rs].set(
                False, mode="drop")
            out["slot_start"] = state["slot_start"].at[:, rs].set(
                EMPTY_START, mode="drop")
            return out

        def extract_reset_local(state, slots):
            packed = _extract_slots_local(state, slots)
            return _reset_slots_local(state, slots), packed

        self.extract_reset_slots = jax.jit(jax.shard_map(
            extract_reset_local, mesh=mesh,
            in_specs=(spec_tree, P()),
            out_specs=(spec_tree, P(None, None, key_axis)),
            check_vma=False))

        self.extract_slots = jax.jit(jax.shard_map(
            _extract_slots_local, mesh=mesh,
            in_specs=(spec_tree, P()),
            out_specs=P(None, None, key_axis), check_vma=False))

        self.reset_slots = jax.jit(jax.shard_map(
            _reset_slots_local, mesh=mesh,
            in_specs=(spec_tree, P()),
            out_specs=spec_tree, check_vma=False))

        max_out = self.max_out

        def touched_local(state):
            # changelog across shards: merge the full lattice over `data`
            # (the one drain that pays a whole-lattice collective), then
            # enumerate per key-shard
            mask = jax.lax.pmax(state["touched"][0].astype(jnp.int32),
                                data_axis).astype(jnp.bool_)
            n = jnp.sum(mask.astype(jnp.int32))
            kidx, sidx = jnp.nonzero(mask, size=max_out, fill_value=0)
            col = {}
            for k, v in state.items():
                if k in ("slot_start", "touched"):
                    continue
                m = _MERGE[merge[k]](v[0], data_axis)
                col[k] = m[kidx, sidx]
            outs = finalize_column(local_spec, col)
            ws_merged = jax.lax.pmax(state["slot_start"][0], data_axis)
            valid = jnp.arange(max_out) < n
            out_state = dict(state)
            out_state["touched"] = jnp.zeros_like(state["touched"])
            kid_global = kidx + key_offset()
            packed = lattice.pack_touched_rows(
                local_spec, n, kid_global,
                jnp.where(valid, ws_merged[sidx], 0), outs, max_out)
            return out_state, packed[None]

        # packed per-key-shard buffers stacked on a leading axis
        self.extract_touched = jax.jit(jax.shard_map(
            touched_local, mesh=mesh,
            in_specs=(spec_tree,),
            out_specs=(spec_tree, P(key_axis)), check_vma=False))

# ---- key-sharded interval join ----------------------------------------------
#
# The shard_map mirror of engine.lattice's interval-join kernels: each
# key shard owns the join-key codes with ``code % n_shards == shard``,
# holds its own slice of both side stores, probes/inserts only the
# batch records it owns (the batch is replicated along the key axis —
# the ownership mask does the routing, like the aggregation lattice),
# and the per-shard match buffers CONCATENATE over ICI into one
# [rows, n_shards * match_cap] buffer before the single host fetch.
# Per-shard headers sit at column s * match_cap.


class ShardedJoinLattice:
    """Both sides of one interval join, key-sharded over a mesh axis.

    Capacities are PER SHARD. Drop-in twin of the single-chip kernels:
    ``probe_insert(mine, other, batch, n, within, cutoff)`` returns
    (mine', packed [rows, n_shards * match_cap]); ``evict(left, right,
    cutoff, delta)`` compacts both sides per shard and returns the
    per-shard live counts [n_shards, 2]. Kernels are built lazily and
    cached per (batch capacity, match capacity) — the sharded mirror of
    the lru-cached single-chip factories — so the executor's sticky
    capacity ladders reuse compiled shapes instead of retracing.

    ``probe_insert_step`` is the fully fused form: the per-shard match
    feed is CONCATenated over ICI (one ``all_gather`` along the key
    axis — the only collective in the hot path) and scattered straight
    into the already-sharded downstream aggregate lattice, so matched
    pairs never leave the device."""

    def __init__(self, mesh: Mesh, key_axis: str, cap: int, bcap: int,
                 match_cap: int, n_cols_l: int, n_cols_r: int):
        self.mesh = mesh
        self.key_axis = key_axis
        self.n_shards = mesh.shape[key_axis]
        self.cap = cap
        self.bcap = bcap
        self.match_cap = match_cap
        self.n_cols = {"l": n_cols_l, "r": n_cols_r}
        self._store_spec = {k: P(key_axis) for k in ("code", "ts",
                                                     "flags", "cols")}
        self._probe_kerns: dict = {}
        self._probe_only_kerns: dict = {}
        self._evict_kerns: dict = {}
        self._fused_kerns: dict = {}

    def store_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.key_axis))

    def init_store(self, side: str, cap: int | None = None
                   ) -> dict[str, jnp.ndarray]:
        """Per-shard empty stores stacked on a leading shard axis and
        placed with the key-axis sharding."""
        local = lattice.init_join_store(cap or self.cap,
                                        self.n_cols[side])
        out = {}
        for k, v in local.items():
            g = jnp.broadcast_to(v[None], (self.n_shards,) + v.shape)
            out[k] = jax.device_put(g, self.store_sharding())
        return out

    def put_store(self, host: Mapping[str, np.ndarray]):
        """Host planes [n_shards, cap, ...] -> device, key-sharded."""
        return {k: jax.device_put(jnp.asarray(v), self.store_sharding())
                for k, v in host.items()}

    def _build_probe_insert(self, nm: int, bcap: int, match_cap: int):
        mesh, key_axis = self.mesh, self.key_axis
        n_shards = self.n_shards
        store_spec = self._store_spec

        def owned_mask(bcode):
            shard = jax.lax.axis_index(key_axis)
            return (bcode % n_shards) == shard

        def probe_insert_local(mine, other, batch, n, within, cutoff):
            m = {k: v[0] for k, v in mine.items()}
            o = {k: v[0] for k, v in other.items()}
            owned = owned_mask(batch[0])
            packed = lattice._join_probe(o, batch, n, within, cutoff,
                                         bcap, match_cap, nm,
                                         owned=owned)
            new = lattice._join_insert(m, batch, n, bcap, nm,
                                       owned=owned)
            return {k: v[None] for k, v in new.items()}, packed

        # match buffers concatenate along the COLUMN axis: global
        # [rows, n_shards * match_cap], per-shard headers at column
        # s * match_cap
        return jax.jit(jax.shard_map(
            probe_insert_local, mesh=mesh,
            in_specs=(store_spec, store_spec, P(), P(), P(), P()),
            out_specs=(store_spec, P(None, key_axis)),
            check_vma=False))

    def _build_probe_only(self, nm: int, bcap: int, match_cap: int):
        mesh, key_axis = self.mesh, self.key_axis
        n_shards = self.n_shards
        store_spec = self._store_spec

        def probe_only_local(other, batch, n, within, cutoff):
            o = {k: v[0] for k, v in other.items()}
            shard = jax.lax.axis_index(key_axis)
            owned = (batch[0] % n_shards) == shard
            return lattice._join_probe(o, batch, n, within, cutoff,
                                       bcap, match_cap, nm,
                                       owned=owned)

        return jax.jit(jax.shard_map(
            probe_only_local, mesh=mesh,
            in_specs=(store_spec, P(), P(), P(), P()),
            out_specs=P(None, key_axis), check_vma=False))

    def _build_evict(self, cap: int):
        mesh, key_axis = self.mesh, self.key_axis
        store_spec = self._store_spec

        def evict_local(left, right, cutoff, delta):
            def _core(code, ts):
                alive = (code < lattice.JOIN_SENT_CODE) & (ts >= cutoff)
                code2 = jnp.where(alive, code, lattice.JOIN_SENT_CODE)
                ts2 = jnp.where(alive, ts - delta, 0)
                idx = jnp.arange(cap, dtype=jnp.int32)
                return jax.lax.sort((code2, ts2, idx), num_keys=2) + (
                    jnp.sum(alive.astype(jnp.int32)),)

            outs = []
            ns = []
            for st in (left, right):
                scode, sts, order, n = _core(st["code"][0], st["ts"][0])
                outs.append({"code": scode[None], "ts": sts[None],
                             "flags": st["flags"][0][order][None],
                             "cols": st["cols"][0][:, order][None]})
                ns.append(n)
            return outs[0], outs[1], jnp.stack(ns)[None]

        return jax.jit(jax.shard_map(
            evict_local, mesh=mesh,
            in_specs=(store_spec, store_spec, P(), P()),
            out_specs=(store_spec, store_spec, P(key_axis)),
            check_vma=False))

    def _build_probe_insert_step(self, nm: int, inner: ShardedLattice,
                                 feed_plan, nulls_plan, filter_nulls,
                                 bcap: int, match_cap: int):
        mesh, key_axis = self.mesh, self.key_axis
        n_shards = self.n_shards
        store_spec = self._store_spec
        spec_tree = {k: inner.state_spec(k)
                     for k in lattice.init_state(inner.spec)}
        local_step = inner._local_step
        Kl = inner.local_spec.n_keys
        n_data, data_axis = inner.n_data, inner.data_axis
        inner_key = inner.key_axis
        mc = match_cap

        def join_step_local(mine, other, batch, n, within, cutoff,
                            inner_state, wm_rel, ts_off):
            m = {k: v[0] for k, v in mine.items()}
            o = {k: v[0] for k, v in other.items()}
            owned = ((batch[0] % n_shards)
                     == jax.lax.axis_index(key_axis))
            total, kid, jts, valid, cols = lattice._join_match_feed(
                o, batch, n, within, cutoff, bcap, mc,
                feed_plan, nulls_plan, filter_nulls, owned=owned)
            # ICI concat point: the per-shard match segments gather
            # into one [n_shards * match_cap] feed, replicated along
            # the key axis so every key shard sees every match and the
            # ownership scatter below re-routes by AGGREGATE key
            # (join key and group key need not shard alike)
            kid = jax.lax.all_gather(kid, key_axis, tiled=True)
            jts = jax.lax.all_gather(jts, key_axis, tiled=True)
            valid = jax.lax.all_gather(valid, key_axis, tiled=True)
            cols = {k: jax.lax.all_gather(v, key_axis, tiled=True)
                    for k, v in cols.items()}
            midx = jnp.arange(n_shards * mc, dtype=jnp.int32)
            if n_data > 1:
                dmine = ((midx % n_data)
                         == jax.lax.axis_index(data_axis))
            else:
                dmine = jnp.ones_like(midx, dtype=jnp.bool_)
            off = (jax.lax.axis_index(inner_key) * Kl
                   if inner_key else 0)
            kid_l = kid - off
            ok = valid & dmine & (kid_l >= 0) & (kid_l < Kl)
            loc = {k: v[0] for k, v in inner_state.items()}
            new_inner = local_step(loc, wm_rel, kid_l, jts + ts_off,
                                   ok, cols,
                                   slot_valid=valid & dmine)
            new_mine = lattice._join_insert(m, batch, n, bcap, nm,
                                            owned=owned)
            return ({k: v[None] for k, v in new_mine.items()},
                    {k: v[None] for k, v in new_inner.items()},
                    total[None])

        return jax.jit(jax.shard_map(
            join_step_local, mesh=mesh,
            in_specs=(store_spec, store_spec, P(), P(), P(), P(),
                      spec_tree, P(), P()),
            out_specs=(store_spec, spec_tree, P(key_axis)),
            check_vma=False))

    def probe_insert(self, side: str, mine, other, batch, n, within,
                     cutoff, match_cap: int | None = None):
        mc = self.match_cap if match_cap is None else match_cap
        key = (side, batch.shape[1], mc)
        fn = self._probe_kerns.get(key)
        if fn is None:
            fn = self._probe_kerns[key] = self._build_probe_insert(
                self.n_cols[side], batch.shape[1], mc)
        return fn(mine, other, batch, n, within, cutoff)

    def probe_only(self, side: str, other, batch, n, within, cutoff,
                   match_cap: int):
        key = (side, batch.shape[1], match_cap)
        fn = self._probe_only_kerns.get(key)
        if fn is None:
            fn = self._probe_only_kerns[key] = self._build_probe_only(
                self.n_cols[side], batch.shape[1], match_cap)
        return fn(other, batch, n, within, cutoff)

    def probe_insert_step(self, side: str, inner: ShardedLattice,
                          mine, other, batch, n, within, cutoff,
                          inner_state, wm_rel, ts_off, *,
                          feed_plan, nulls_plan, filter_nulls,
                          match_cap: int | None = None):
        """Fused probe + insert + downstream-aggregate scatter, one
        dispatch; returns (mine', inner_state', per-shard totals
        i32[n_shards]). `inner` is the query's ShardedLattice (same
        mesh); the fused kernel is cached per (side, inner, shapes)."""
        mc = self.match_cap if match_cap is None else match_cap
        key = (side, inner, batch.shape[1], mc, feed_plan,
               nulls_plan, filter_nulls)
        fn = self._fused_kerns.get(key)
        if fn is None:
            fn = self._fused_kerns[key] = self._build_probe_insert_step(
                self.n_cols[side], inner, feed_plan, nulls_plan,
                filter_nulls, batch.shape[1], mc)
        return fn(mine, other, batch, n, within, cutoff, inner_state,
                  wm_rel, ts_off)

    def evict(self, left, right, cutoff, delta):
        cap = left["code"].shape[1]
        fn = self._evict_kerns.get(cap)
        if fn is None:
            fn = self._evict_kerns[cap] = self._build_evict(cap)
        return fn(left, right, cutoff, delta)

    def unpack_matches(self, packed: np.ndarray, side: str):
        """Flatten the shard-concatenated match buffer into host arrays
        in shard order: (total, kid, jts_rel, my_flags, other_flags,
        my_cols, other_cols) — the sharded twin of
        lattice.unpack_join_matches. `total` sums the per-shard headers;
        truncation per shard is visible as total > len(kid)."""
        nm = self.n_cols[side]
        match_cap = packed.shape[1] // self.n_shards
        parts = []
        total = 0
        for s in range(self.n_shards):
            seg = packed[:, s * match_cap:(s + 1) * match_cap]
            t, kid, jts, mf, of, mc, oc = lattice.unpack_join_matches(
                seg, nm)
            total += t
            parts.append((kid, jts, mf, of, mc, oc))
        return (total,
                np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
                np.concatenate([p[2] for p in parts]),
                np.concatenate([p[3] for p in parts]),
                np.concatenate([p[4] for p in parts], axis=1),
                np.concatenate([p[5] for p in parts], axis=1))


# ---- key-sharded session arena ----------------------------------------------
#
# Session chain merge is KEY-LOCAL (a session never spans keys), so the
# arena shards exactly like the join stores: each key shard keeps its
# own (code, t0)-sorted arena slice for the codes with
# ``code % n_shards == shard``, the packed batch / segment feed is
# replicated along the key axis, and an ownership mask does the routing
# — unowned records have their valid bit cleared (record mode) or their
# segment code rewritten to the sentinel (segment mode), which the
# single-chip kernels already treat as "drop" (their scatters are all
# mode="drop" at dest=cap). Zero collectives anywhere: step, merge,
# extract and remap are all embarrassingly per-shard; the host keeps
# the global interval mirror plus a per-shard slot index so late-drop
# and close decisions still resolve with zero device syncs.


class ShardedSessionLattice:
    """The session arena of one query, key-sharded over a mesh axis.

    Capacities are PER SHARD. Kernels wrap the lru-cached single-chip
    session factories under shard_map, built lazily and cached per
    shape so the executor's sticky capacity ladders reuse compiled
    shapes instead of retracing."""

    def __init__(self, mesh: Mesh, key_axis: str, spec, schema,
                 layout):
        self.mesh = mesh
        self.key_axis = key_axis
        self.n_shards = mesh.shape[key_axis]
        self.spec = spec
        self.schema = schema
        self.layout = layout
        self._plane_names = tuple(lattice.session_plane_np(spec, 1))
        self._arena_spec = {k: P(key_axis) for k in self._plane_names}
        self._step_kerns: dict = {}
        self._merge_kerns: dict = {}
        self._extract_kerns: dict = {}
        self._remap_kerns: dict = {}

    def arena_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.key_axis))

    def init_arena(self, cap: int) -> dict[str, jnp.ndarray]:
        """Per-shard empty arenas stacked on a leading shard axis and
        placed with the key-axis sharding."""
        local = lattice.session_plane_np(self.spec, cap)
        return {k: jax.device_put(
            jnp.broadcast_to(jnp.asarray(v)[None],
                             (self.n_shards,) + v.shape),
            self.arena_sharding()) for k, v in local.items()}

    def put_arena(self, host: Mapping[str, np.ndarray]):
        """Host planes [n_shards, cap, ...] -> device, key-sharded."""
        return {k: jax.device_put(jnp.asarray(v), self.arena_sharding())
                for k, v in host.items()}

    def grow_arena(self, arena, new_cap: int):
        """Copy every shard's slice into a fresh wider arena (identity
        fill past the old capacity), like lattice.grow_session_arena."""
        fresh = lattice.session_plane_np(self.spec, new_cap)
        out = {}
        for k, v in arena.items():
            g = jnp.broadcast_to(jnp.asarray(fresh[k])[None],
                                 (self.n_shards,) + fresh[k].shape)
            out[k] = jax.device_put(g.at[:, :v.shape[1]].set(v),
                                    self.arena_sharding())
        return out

    def _build_step(self, cap: int, bcap: int):
        base = lattice.session_step_kernel(self.spec, self.schema,
                                           self.layout, cap, bcap)
        mesh, key_axis = self.mesh, self.key_axis
        n_shards = self.n_shards
        aspec = self._arena_spec

        def session_step_local(arena, packed, gap, close_cut, delta):
            loc = {k: v[0] for k, v in arena.items()}
            owned = ((packed[0] % n_shards)
                     == jax.lax.axis_index(key_axis))
            # ownership routing: clear the valid bit (flags bit 0) of
            # records other shards own — the kernel maps invalid
            # records to the sentinel code and drops their scatters
            routed = packed.at[2].set(
                jnp.where(owned, packed[2], packed[2] & ~1))
            new = base(loc, routed, gap, close_cut, delta)
            return {k: v[None] for k, v in new.items()}

        return jax.jit(jax.shard_map(
            session_step_local, mesh=mesh,
            in_specs=(aspec, P(), P(), P(), P()),
            out_specs=aspec, check_vma=False))

    def _build_merge(self, cap: int, scap: int, seg_keys: tuple):
        base = lattice.session_merge_kernel(self.spec, cap, scap)
        mesh, key_axis = self.mesh, self.key_axis
        n_shards = self.n_shards
        aspec = self._arena_spec
        seg_spec = {k: P() for k in seg_keys}

        def session_merge_local(arena, seg, gap, close_cut, delta):
            loc = {k: v[0] for k, v in arena.items()}
            owned = ((seg["code"] % n_shards)
                     == jax.lax.axis_index(key_axis))
            s2 = dict(seg)
            s2["code"] = jnp.where(
                owned & (seg["code"] < lattice.SESSION_SENT_CODE),
                seg["code"], lattice.SESSION_SENT_CODE)
            new = base(loc, s2, gap, close_cut, delta)
            return {k: v[None] for k, v in new.items()}

        return jax.jit(jax.shard_map(
            session_merge_local, mesh=mesh,
            in_specs=(aspec, seg_spec, P(), P(), P()),
            out_specs=aspec, check_vma=False))

    def _build_extract(self, cap: int, pcap: int):
        base = lattice.session_extract_kernel(self.spec, cap, pcap)
        mesh, key_axis = self.mesh, self.key_axis
        aspec = self._arena_spec

        def session_extract_local(arena, slots):
            loc = {k: v[0] for k, v in arena.items()}
            return base(loc, slots[0])[None]

        return jax.jit(jax.shard_map(
            session_extract_local, mesh=mesh,
            in_specs=(aspec, P(key_axis)),
            out_specs=P(key_axis), check_vma=False))

    def _build_remap(self, cap: int, lcap: int):
        base = lattice.session_remap_kernel(cap, lcap)
        mesh = self.mesh
        aspec = self._arena_spec

        def session_remap_local(arena, lut):
            loc = {k: v[0] for k, v in arena.items()}
            new = base(loc, lut)
            return {k: v[None] for k, v in new.items()}

        return jax.jit(jax.shard_map(
            session_remap_local, mesh=mesh,
            in_specs=(aspec, P()),
            out_specs=aspec, check_vma=False))

    def step(self, arena, packed, gap, close_cut, delta):
        """Record-mode micro-batch: arena' — one dispatch, no fetch."""
        cap, bcap = arena["code"].shape[1], packed.shape[1]
        fn = self._step_kerns.get((cap, bcap))
        if fn is None:
            fn = self._step_kerns[(cap, bcap)] = self._build_step(
                cap, bcap)
        return fn(arena, packed, gap, close_cut, delta)

    def merge(self, arena, seg, gap, close_cut, delta):
        """Segment-mode micro-batch: arena' — one dispatch, no fetch."""
        cap = arena["code"].shape[1]
        scap = seg["code"].shape[0]
        seg_keys = tuple(sorted(seg))
        fn = self._merge_kerns.get((cap, scap, seg_keys))
        if fn is None:
            fn = self._merge_kerns[(cap, scap, seg_keys)] = \
                self._build_merge(cap, scap, seg_keys)
        return fn(arena, seg, gap, close_cut, delta)

    def extract(self, arena, slots):
        """Finalized rows for per-shard slot lists [n_shards, pcap]
        (-1 pads) -> packed [n_shards, 1 + n_aggs, pcap]."""
        cap, pcap = arena["code"].shape[1], slots.shape[1]
        fn = self._extract_kerns.get((cap, pcap))
        if fn is None:
            fn = self._extract_kerns[(cap, pcap)] = self._build_extract(
                cap, pcap)
        return fn(arena, slots)

    def remap(self, arena, lut):
        """Rewrite arena codes through a replicated LUT (compaction).
        The LUT must be residue-class preserving (new % n_shards ==
        old % n_shards) so entries never change owner shard."""
        cap, lcap = arena["code"].shape[1], lut.shape[0]
        fn = self._remap_kerns.get((cap, lcap))
        if fn is None:
            fn = self._remap_kerns[(cap, lcap)] = self._build_remap(
                cap, lcap)
        return fn(arena, lut)
