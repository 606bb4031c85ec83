"""ArrayTable — 1-D dense distributed table.

Reference capability (not copied): contiguous range-sharded 1-D table across
servers, whole-table Get/Add only, server-side updater application
(``src/table/array_table.cpp``, ``include/multiverso/table/array_table.h``).

TPU-native re-design: the table is ONE ``jax.Array`` in HBM, sharded over the
``server`` mesh axis (padded to shard-divisible length); the reference's
client-side ``Partition`` (slicing the value blob per server rank) does not
exist — XLA partitions the donated jitted update. Optimizer state shards
live beside the data with identical layout. ``get_device()`` exposes the
sharded device array for zero-copy use inside jitted training steps — the
TPU-era fast path that host-RAM parameter servers could not offer.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu import log
from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.runtime.zoo import Zoo
from multiverso_tpu.tables.base import ServerTable, WorkerTable
from multiverso_tpu.utils import async_upload
from multiverso_tpu.updaters import (AddOption, GetOption, SGDUpdater,
                                     Updater, get_updater)


def _make_whole_update(updater: Updater, jit: bool = True):
    """One whole-table update closed over the updater. Jitted+donated so
    the HBM buffers are reused in place; ``jit=False`` returns the raw
    traceable function for embedding in larger fused jits."""

    def f(data, states, delta, worker, scalars):
        if updater.per_worker_state:
            sliced = {k: jax.lax.dynamic_index_in_dim(v, worker, 0, keepdims=False)
                      for k, v in states.items()}
        else:
            sliced = {k: v[0] for k, v in states.items()}
        new_data, new_sliced = updater.apply(data, sliced, delta, scalars)
        if updater.per_worker_state:
            new_states = {k: jax.lax.dynamic_update_index_in_dim(states[k], new_sliced[k], worker, 0)
                          for k in states}
        else:
            new_states = {k: new_sliced[k][None] for k in states}
        return new_data, new_states

    return jax.jit(f, donate_argnums=(0, 1)) if jit else f


class ArrayServer(ServerTable):
    def __init__(self, size: int, dtype: Any = np.float32,
                 updater_type: str = "", num_workers: Optional[int] = None,
                 init_value: Optional[np.ndarray] = None) -> None:
        super().__init__()
        zoo = Zoo.instance()
        self.size = int(size)
        self.dtype = np.dtype(dtype)
        self.mesh = zoo.mesh
        num_shards = zoo.num_servers
        self.num_workers = num_workers if num_workers is not None else zoo.num_workers
        self.padded = mesh_lib.pad_to_multiple(self.size, num_shards)
        sharding = mesh_lib.table_sharding(self.mesh, ndim=1)

        init = np.zeros(self.padded, dtype=self.dtype)
        if init_value is not None:
            init[: self.size] = np.asarray(init_value, dtype=self.dtype)
        self.data = jax.device_put(init, sharding)

        self.updater = get_updater(self.dtype, updater_type)
        if self.updater.row_state:
            log.fatal("updater_type %s keeps one value of state a row: it "
                      "serves matrix tables, not an array table",
                      self.updater.name)
        worker_dim = self.num_workers if self.updater.per_worker_state else 1
        self.states: Dict[str, jax.Array] = {}
        for name, (shape_suffix, sdtype) in self.updater.state_spec(
                (self.padded,), self.dtype).items():
            s_shard = mesh_lib.table_sharding(self.mesh, ndim=2, shard_dim=1)
            self.states[name] = jax.device_put(
                np.zeros((worker_dim,) + tuple(shape_suffix), dtype=sdtype), s_shard)

        self._update = _make_whole_update(self.updater)
        self._codecs: Dict = {}  # leaf-signature -> (to_flat, from_flat)

    # -- server ops --------------------------------------------------------
    def merge_add_requests(self, requests):
        """Whole-array host deltas sum into ONE update — linear updaters
        only (a stateful updater applied once to a summed delta is a
        different operator than N sequential applies). The fused
        add+get form (3-tuple), leaf-tagged forms, and device-resident
        deltas all refuse: their replies/payloads are per-request."""
        if type(self.updater) not in (Updater, SGDUpdater):
            return None
        total = None
        consumed = 0
        for request in requests:
            if not (isinstance(request, tuple) and len(request) == 2):
                break
            delta, _option = request
            if delta is None or isinstance(delta, jax.Array):
                break
            arr = np.asarray(delta, dtype=self.dtype).reshape(-1)
            if arr.size != self.size:
                break  # per-message path reports the real error
            total = arr.astype(self.dtype, copy=True) if total is None \
                else total + arr
            consumed += 1
        if total is None:
            return None
        return (total, requests[0][1]), int(total.size), consumed

    def _leaf_codec(self, leaves):
        """jitted (to_flat, from_flat) for a list-of-arrays signature.
        from_flat's outputs are committed to ONE device, the mesh's first:
        worker threads then compute on single-device arrays only, so every
        cross-shard collective stays on the dispatcher thread. Concurrent
        sharded executions from N worker threads deadlock the CPU test
        mesh's rendezvous; on a real multi-chip mesh the effect is not
        measured."""
        key = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        codec = self._codecs.get(key)
        if codec is not None:
            return codec
        shapes = [tuple(l.shape) for l in leaves]
        dtypes = [l.dtype for l in leaves]
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        if sum(sizes) != self.size:
            log.fatal("leaf signature totals %d, table size %d",
                      sum(sizes), self.size)
        pad, dtype = self.padded - self.size, self.dtype

        def to_flat_impl(ls):
            flat = (jnp.concatenate(
                [jnp.ravel(x).astype(dtype) for x in ls])
                if ls else jnp.zeros(0, dtype))
            return jnp.pad(flat, (0, pad)) if pad else flat

        to_flat = jax.jit(to_flat_impl)

        from jax.sharding import SingleDeviceSharding
        dev = SingleDeviceSharding(self.mesh.devices.flat[0])
        # on a 1-device mesh sharded == single device, so both boundary
        # transfers would be one no-op dispatch per leaf — skip them
        multi = self.mesh.size > 1

        def split_impl(flat):
            out, n = [], 0
            for shape, dt, size in zip(shapes, dtypes, sizes):
                out.append(flat[n:n + size].reshape(shape).astype(dt))
                n += size
            return out

        split = jax.jit(split_impl)

        def from_flat(flat):
            # split stays sharded in-jit (jit rejects mixed device sets in
            # out_shardings); the gather to ONE device is an explicit
            # transfer issued here, on the dispatcher thread
            leaves = split(flat)
            return jax.device_put(leaves, dev) if multi else leaves

        fused = fused_sync = None
        if not multi:
            # single-device mesh: the whole sync — flatten, update,
            # access, split — is ONE compiled dispatch (mixed device sets
            # block this on sharded meshes, which use the staged path)
            update_raw = _make_whole_update(self.updater, jit=False)
            access = self.updater.access

            def fused_impl(data, states, ls, worker, scalars):
                data, states = update_raw(data, states, to_flat_impl(ls),
                                          worker, scalars)
                return data, states, split_impl(access(data))

            fused = jax.jit(fused_impl, donate_argnums=(0, 1))

            def fused_sync_impl(data, states, new_ls, last_ls, worker,
                                scalars):
                # delta computed HERE (not in a worker-thread jit) so the
                # whole ASGD sync — delta, update, access, split, baseline
                # copy — is ONE dispatch: each dispatch has a fixed host
                # submission cost (not measured on the current machine)
                delta = to_flat_impl(new_ls) - to_flat_impl(last_ls)
                data, states = update_raw(data, states, delta, worker,
                                          scalars)
                merged = split_impl(access(data))
                # the baseline is a DISTINCT buffer set: callers donate the
                # merged leaves into their train step, which would delete a
                # shared baseline out from under the next delta
                baseline = [jnp.copy(x) for x in merged]
                return data, states, merged, baseline

            # donate last_ls too (argnum 3): the view owns those buffers
            # exclusively and replaces them with `baseline` on return
            fused_sync = jax.jit(fused_sync_impl, donate_argnums=(0, 1, 3))

            def fused_push_impl(data, states, new_ls, last_ls, worker,
                                scalars):
                # reply-free pair push for round-gated/deferred servers:
                # no merged split, no baseline copy — the client pulls
                # through a properly gated Get instead
                delta = to_flat_impl(new_ls) - to_flat_impl(last_ls)
                return update_raw(data, states, delta, worker, scalars)

            fused_push = jax.jit(fused_push_impl, donate_argnums=(0, 1, 3))
        else:
            fused_push = None

        def pair_delta_impl(new_ls, last_ls):
            return to_flat_impl(new_ls) - to_flat_impl(last_ls)

        pair_delta = jax.jit(pair_delta_impl)
        # distinct-buffer device-local copy (staged multi-device path):
        # far cheaper than a second split + cross-device gather
        copy_leaves = jax.jit(lambda ls: [jnp.copy(x) for x in ls])

        codec = (to_flat, from_flat, fused, fused_sync, pair_delta,
                 fused_push, copy_leaves)
        self._codecs[key] = codec
        return codec

    def process_add(self, request) -> Optional[list]:
        want_get = False
        kind = request[0] if isinstance(request[0], str) else None
        if kind == "leaves_sync":
            # one-dispatch whole-model sync: (new, last) leaf lists in,
            # (merged, baseline) out — see fused_sync_impl in _leaf_codec
            _, new_ls, last_ls, option = request
            option = option or AddOption()
            (_, from_flat, _, fused_sync, pair_delta, _,
             copy_leaves) = self._leaf_codec(list(new_ls))
            worker, scalars = self._option_consts(option)
            if fused_sync is not None:  # single-device: one dispatch
                self.data, self.states, merged, baseline = fused_sync(
                    self.data, self.states, list(new_ls), list(last_ls),
                    worker, scalars)
                return (merged, baseline)
            # staged multi-device path: jitted pair-delta, scatter to the
            # table sharding, one from_flat gather, then a device-local
            # copy for the distinct baseline buffer set
            delta = jax.device_put(
                pair_delta(list(new_ls), list(last_ls)),
                mesh_lib.table_sharding(self.mesh, ndim=1))
            self.data, self.states = self._update(self.data, self.states,
                                                  delta, worker, scalars)
            merged = from_flat(self.updater.access(self.data))
            return (merged, copy_leaves(merged))
        if kind == "leaves_push":
            # reply-free pair push (round-gated/deferred servers): apply
            # new-last, materialize nothing — the client follows with a
            # properly gated Get
            _, new_ls, last_ls, option = request
            option = option or AddOption()
            _, _, _, _, pair_delta, fused_push, _ = self._leaf_codec(
                list(new_ls))
            worker, scalars = self._option_consts(option)
            if fused_push is not None:  # single-device: one dispatch
                self.data, self.states = fused_push(
                    self.data, self.states, list(new_ls), list(last_ls),
                    worker, scalars)
                return None
            delta = jax.device_put(
                pair_delta(list(new_ls), list(last_ls)),
                mesh_lib.table_sharding(self.mesh, ndim=1))
            self.data, self.states = self._update(self.data, self.states,
                                                  delta, worker, scalars)
            return None
        if kind == "leaves":
            # fused whole-model sync: delta arrives as the caller's leaf
            # list, the merged value returns the same way — one hop, all
            # sharded math right here on the dispatcher thread
            _, leaves, option = request
            option = option or AddOption()
            to_flat, from_flat, fused, _, _, _, _ = self._leaf_codec(leaves)
            worker, scalars = self._option_consts(option)
            if fused is not None:  # single-device: one compiled dispatch
                self.data, self.states, out = fused(
                    self.data, self.states, list(leaves), worker, scalars)
                return out
            # staged multi-device path: explicit scatter to the table
            # sharding (the jitted update can't take mixed device sets)
            delta = jax.device_put(
                to_flat(list(leaves)),
                mesh_lib.table_sharding(self.mesh, ndim=1))
            self.data, self.states = self._update(self.data, self.states,
                                                  delta, worker, scalars)
            return from_flat(self.updater.access(self.data))
        if len(request) == 3:  # fused add+get (flat device sync path)
            delta, option, want_get = request
        else:
            delta, option = request
        option = option or AddOption()
        # host deltas are normalized to device arrays up front; a
        # jax.Array input never touches the host (the TPU-era ASGD path —
        # param sync is HBM-to-HBM)
        if not isinstance(delta, jax.Array):
            host = np.asarray(delta, dtype=self.dtype)
            if host is delta:
                # asarray was a no-op, so the enqueued upload would read
                # the CALLER's buffer — which it may mutate the moment
                # add_async returns. Snapshot it before going async.
                host = host.copy()
            delta = async_upload(host)
        delta = delta.reshape(-1).astype(self.dtype)
        if delta.size != self.size:
            log.fatal("ArrayTable.add: delta size %d != table size %d",
                      delta.size, self.size)
        if self.padded != self.size:
            delta = jnp.pad(delta, (0, self.padded - self.size))
        # administrative access (worker id -1) charges slot 0, not slot n-1
        worker, scalars = self._option_consts(option)
        self.data, self.states = self._update(self.data, self.states,
                                              delta, worker, scalars)
        if want_get:
            # fused reply: the post-add global value, still in HBM — one
            # dispatcher hop for the whole ASGD sync instead of two
            return self._device_value()
        return None

    def _device_value(self) -> jax.Array:
        out = self.updater.access(self.data)[: self.size]
        # jnp.copy: with an identity access and size == padded the slice
        # can alias self.data, whose buffer the NEXT add donates — the
        # caller's reply would be deleted out from under it
        return jnp.copy(out)

    def process_get(self, request) -> np.ndarray:
        device_out = False
        if isinstance(request, tuple):
            if isinstance(request[0], str) and request[0] == "leaves":
                # leaf-shaped device get: reply mirrors the template's
                # shapes/dtypes, committed single-device (see _leaf_codec)
                _, template, _option = request
                _, from_flat, _, _, _, _, _ = self._leaf_codec(template)
                return from_flat(self.updater.access(self.data))
            request, device_out = request  # in-process device-out form
        if device_out:
            return self._device_value()  # stays in HBM, donation-safe
        out = self.updater.access(self.data)
        return self._host_read(out)[: self.size]

    def remote_spec(self):
        return {"kind": "array", "size": self.size, "dtype": self.dtype.str}

    # -- checkpoint --------------------------------------------------------
    def store(self, stream) -> None:
        from multiverso_tpu.checkpoint import write_array, write_state_dict
        write_array(stream, self._host_read(self.data)[: self.size])
        write_state_dict(stream, {
            name: self._host_read(arr)[:, : self.size]
            for name, arr in self.states.items()})

    def load(self, stream) -> None:
        from multiverso_tpu.checkpoint import read_array, read_state_dict
        arr = read_array(stream)
        if arr.size != self.size:
            log.fatal("ArrayTable.load: size mismatch %d != %d", arr.size, self.size)
        padded = np.zeros(self.padded, dtype=self.dtype)
        padded[: self.size] = arr.astype(self.dtype)
        self.data = jax.device_put(padded, mesh_lib.table_sharding(self.mesh, ndim=1))
        loaded = read_state_dict(stream)
        s_shard = mesh_lib.table_sharding(self.mesh, ndim=2, shard_dim=1)
        for name, cur in self.states.items():
            got = loaded.get(name)
            if got is None:
                continue  # v1 checkpoint: that state resets (pre-v2 behavior)
            if got.shape[0] != cur.shape[0]:
                # per-worker state from a world with a different worker
                # count: elastic restarts keep working — reset like v1
                log.info("checkpoint: %s worker dim %d != %d; resetting "
                         "that updater state", name, got.shape[0],
                         cur.shape[0])
                continue
            full = np.zeros(cur.shape, np.dtype(cur.dtype))
            full[:, : self.size] = got
            self.states[name] = jax.device_put(full, s_shard)

    # -- live migration (shard/reshard.py) ---------------------------------
    def extract_range(self, lo: int, hi: int):
        """Raw values of shard-local elements [lo, hi) — the migration
        transfer unit (updater state excluded; documented reset)."""
        return self._host_read(self.data)[lo:hi]

    def absorb_range(self, start: int, values) -> None:
        """Install raw values at [start, start+len), bypassing updaters —
        the recipient side of extract_range."""
        values = np.asarray(values, dtype=self.dtype).reshape(-1)
        n = values.size
        if start < 0 or start + n > self.size:
            log.fatal("absorb_range [%d, %d) outside [0, %d)",
                      start, start + n, self.size)
        padded = np.array(self._host_read(self.data))
        padded[start:start + n] = values
        self.data = jax.device_put(
            padded, mesh_lib.table_sharding(self.mesh, ndim=1))


class ArrayWorker(WorkerTable):
    """Client proxy for a 1-D dense table (whole-table Get/Add)."""

    def __init__(self, size: int, dtype: Any = np.float32,
                 updater_type: str = "",
                 init_value: Optional[np.ndarray] = None,
                 server: Optional[ArrayServer] = None) -> None:
        super().__init__()
        self.size = int(size)
        self.dtype = np.dtype(dtype)
        self._server_table = server or ArrayServer(
            size, dtype, updater_type, init_value=init_value)
        self._register(self._server_table)
        if Zoo.instance().multihost is not None:
            # device IO exchanges jax.Arrays with the dispatcher; lockstep
            # descriptors must be host-serializable — host paths only
            self.supports_device_io = False

    # -- API (mirrors reference ArrayWorker + python binding handler) -------
    def get(self, option: Optional[GetOption] = None) -> np.ndarray:
        return super().get(option)

    def get_async(self, option: Optional[GetOption] = None) -> int:
        return super().get_async(option)

    def add(self, delta: np.ndarray, option: Optional[AddOption] = None) -> None:
        option = self._default_option(option)
        super().add((delta, option))

    def add_async(self, delta: np.ndarray, option: Optional[AddOption] = None) -> int:
        option = self._default_option(option)
        return super().add_async((delta, option))

    def _default_option(self, option: Optional[AddOption]) -> AddOption:
        if option is None:
            option = AddOption()
            option.worker_id = self._channel.worker_id()
        return option

    # -- TPU-era fast path -------------------------------------------------
    supports_device_io = True

    def get_device(self) -> jax.Array:
        """The live sharded device array (valid until the next add)."""
        return self._server_table.data

    def get_device_async(self, option: Optional[GetOption] = None) -> int:
        """Dispatcher-ordered Get whose reply STAYS in HBM: a (size,)
        jax.Array reflecting every add queued before it. Unlike
        :meth:`get_device` this is safe against concurrent adds."""
        self._require_device_io()
        return super().get_async((option, True))

    def add_device_async(self, delta: "jax.Array",
                         option: Optional[AddOption] = None) -> int:
        """Async add of a DEVICE-resident (size,) delta — no host copy;
        the dispatcher applies it via the same jitted updater."""
        self._require_device_io()
        option = self._default_option(option)
        return super().add_async((delta, option))

    def sync_device_async(self, delta: "jax.Array",
                          option: Optional[AddOption] = None) -> int:
        """Fused device add+get: ONE dispatcher hop whose reply is the
        post-add global value in HBM. Deferred-apply servers (BSP /
        deterministic) reply None — callers fall back to an explicit
        get_device_async."""
        self._require_device_io()
        option = self._default_option(option)
        return super().add_async((delta, option, True))

    def sync_leaves_async(self, delta_leaves: list,
                          option: Optional[AddOption] = None,
                          last_leaves: Optional[list] = None) -> int:
        """Fused whole-model sync in the caller's own leaf shapes: ONE
        dispatcher hop; the reply is the merged value as a list of
        SINGLE-DEVICE arrays (safe for concurrent worker-thread compute —
        see ``ArrayServer._leaf_codec``). The leaf sizes must total the
        table size. Deferred-apply servers reply None; fall back to
        ``get_leaves_async``.

        With ``last_leaves``, ``delta_leaves`` is instead the NEW value and
        the server computes ``new - last`` in the same dispatch, replying
        ``(merged, baseline)`` where ``baseline`` is a distinct buffer set
        the caller may keep while donating ``merged``. ``last_leaves`` is
        donated — the caller must own those buffers exclusively."""
        self._require_device_io()
        option = self._default_option(option)
        if last_leaves is not None:
            return super().add_async(("leaves_sync", list(delta_leaves),
                                      list(last_leaves), option))
        return super().add_async(("leaves", list(delta_leaves), option))

    def push_leaves_async(self, new_leaves: list, last_leaves: list,
                          option: Optional[AddOption] = None) -> int:
        """Reply-free pair push: the server applies ``new - last`` and
        materializes nothing. For round-gated/deferred servers, where a
        fused merged reply would be discarded anyway — follow with a
        (gated) ``get_leaves_async``. ``last_leaves`` is donated."""
        self._require_device_io()
        option = self._default_option(option)
        return super().add_async(("leaves_push", list(new_leaves),
                                  list(last_leaves), option))

    def get_leaves_async(self, template_leaves: list,
                         option: Optional[GetOption] = None) -> int:
        """Device get shaped like ``template_leaves`` (values unused, only
        shapes/dtypes), single-device committed."""
        self._require_device_io()
        return super().get_async(("leaves", list(template_leaves), option))
