"""FTRL table: ``(z, n)`` a key in HBM, read and stepped by key.

Reference capability (not copied): LogisticRegression defines custom
user-level tables — ``FTRLWorkerTable/FTRLServerTable`` with struct-valued
entries ``FTRLEntry{z, n}`` where the *server* runs the FTRL-proximal update
on a pushed raw gradient and Get materializes weights from (z, n)
(``Applications/LogisticRegression/src/util/ftrl_sparse_table.h:12-90``;
McMahan et al., KDD 2013, Algorithm 1).

TPU-native re-design: ``z`` and ``n`` are two lane-dense float32 arrays of
``size`` keys and some scratch entries, in HBM, beside no weight array at
all: weights are *derived on the device* inside the Get (the closed form),
so the server never stores a stale ``w``. Both ops are **keyed**, one device
program an op (``jit__ftrl_keyed_get`` / ``jit__ftrl_keyed_add`` in a trace):

* a Get gathers ``z`` and ``n`` at the keys named and applies the closed form;
* an Add steps them from the raw gradient (:func:`ftrl_step`, THE rule,
  written once) and writes them back, ``z`` and ``n`` donated.

**Where an Add's step runs** is chosen once, at the table's creation, from
the mesh and the platform, and by the op's bucket at each launch, by the
table's row plan (``tables/row_plan.py``, which launches both ops and fills
their records; the creation log line and every launch record's ``path`` say
which): on ONE device whose platform the Pallas row kernels serve, inside
the lane kernel (``ops/pallas_rows.add_at_lanes``, the one custom call of
``jit__ftrl_keyed_add``, handed :func:`ftrl_step` as its rule: it reads the
rows of 128 the keys live in, computes the step on them where they landed
in VMEM and writes them back; since PR 49 that program gathers no state and
spreads nothing over a row's lanes; since PR 51 a row several keys share is
read, stepped and written ONCE, the distinct rows compacted on the device
in the same program, which returns their count), up to a bucket of
``pallas_rows.PREFETCH_SLOTS`` keys; elsewhere XLA gathers the keys' ``z``
and ``n``, the same function steps them a slot, and XLA's two scatters of
single floats write them, which a 3.53 GB operand prices at 11 ms each.
Both write the same ``n`` to the bit and, where both run XLA's operations
(the CPU, kernel interpreted), the same ``z``; on the chip Mosaic's root
and quotient need not round as XLA's do and ``z`` is held to the
reference's tolerance (``tests/test_ftrl_keyed.py``; ``chip_smoke.py``,
phase ``keyed``). The kernel's module brings
``jax.experimental.pallas``, a second of module code, so nothing here
imports it at the top: the plan loads it, for the table that will launch
it and for nobody else, under the fill of the table's state
(``FTRLServer._make_state``, handed to it).

The keys go up padded to the op's power-of-two bucket with slots aimed at
the scratch key ``size``, ONE form for a Get and an Add, so that a trainer's
push launches on the ids its pull left on the device
(``tables/device_ids.py``: ids sent up from the caller's thread at submit,
and kept; code this table shares with the matrix table). The programs take a
static slice of the bucket, the keys named rounded up to a thirty-second of
it (``live_slots``), so a bucket has at most 16 programs of each kind and
the work follows the keys named.

**A repeated key** in one Add has its gradients summed (float32, in an order
the device chooses: exact for gradients on a binary grid) before the ONE
step the key takes, on the device: the keys are sorted with their gradients
in the Add's program, so no thread of the host sorts anything. It is never
last-writer-wins. Scratch slots carry a zero gradient, which leaves ``(z,
n)`` as they were to the bit.

The whole-array ops this table had (``get()``, ``add(grad)``) are the keyed
ops over every key.

Not served, refused by name: a key outside ``[0, size)`` (on the caller's
thread); a gradient shorter than its keys; a remote client's device IO (its
host forms are served: ``runtime/remote.py``, ``_RemoteFTRLWorker``).
Served and stated: a mesh of several devices takes
XLA's partitioned gather and scatter (the dispatcher sends the ids up; a
device Get's result is committed to the mesh's first device); the ``sync`` /
SSP / deterministic servers serve the ops message by message as the async
server does. An FTRL step is not linear, so Adds never fuse
(``merge_add_requests`` is the base class's: per message) and they do not
commute: the order of acknowledgement is part of the result. The async
server makes that order visible: every reply to an op on this table carries
the table's Add ordinal (``Server._stamp``; ``FTRLWorker.last_ordinal``).
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu import log
from multiverso_tpu.dashboard import Dashboard, span
from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.runtime.message import MsgType, PendingHostRead
from multiverso_tpu.runtime.zoo import Zoo
from multiverso_tpu.tables.base import ServerTable, WorkerTable
from multiverso_tpu.tables.device_ids import (DeviceIdsServer,
                                              DeviceIdsWorker, live_slots,
                                              state_of_slots)
from multiverso_tpu.tables.row_plan import _row_kernel, row_plan
from multiverso_tpu.utils import async_upload, next_pow2

# the smallest bucket: a tile of lanes
_MIN_BUCKET = 128
# keys a piece of a block source's state goes up in (two float32 arrays)
_PIECE_KEYS = mesh_lib.PIECE_BYTES // 8


def ftrl_weights(z: jax.Array, n: jax.Array, alpha: float, beta: float,
                 lambda1: float, lambda2: float) -> jax.Array:
    """Closed-form FTRL-proximal weights from accumulator state."""
    shrunk = jnp.sign(z) * jnp.maximum(jnp.abs(z) - lambda1, 0.0)
    denom = (beta + jnp.sqrt(n)) / alpha + lambda2
    return -shrunk / denom


def ftrl_step(z: jax.Array, n: jax.Array, g: jax.Array, alpha: float,
              beta: float, lambda1: float, lambda2: float
              ) -> Tuple[jax.Array, jax.Array]:
    """One FTRL-Proximal step of ``(z, n)`` from the raw gradient ``g``
    (McMahan et al., Algorithm 1), elementwise, float32: the new ``(z,
    n)``. THE rule, written once: XLA's path calls it on the values it
    gathered a slot, the row kernel traces it on the ``(LANE_GROUP, 128)``
    blocks of ``z`` and ``n`` it has read into VMEM, a distinct row each."""
    grown = n + g * g
    sigma = (jnp.sqrt(grown) - jnp.sqrt(n)) / alpha
    w = ftrl_weights(z, n, alpha, beta, lambda1, lambda2)
    return z + (g - sigma * w), grown


def _summed_by_key(keys: jax.Array, grad: jax.Array, scratch: int):
    """``(keys, grad)`` sorted by key, every slot of a key holding the sum
    of the gradients of all its slots: slots of one key then compute one
    step and write one value. Where no key repeats (a trainer sums its own
    duplicates; the scratch slots, whose gradient is zero, do not count)
    the sums are skipped on the device."""
    keys, grad = jax.lax.sort((keys, grad), num_keys=1)
    first = jnp.concatenate([jnp.ones(1, bool), keys[1:] != keys[:-1]])

    def summed(g):
        run = jnp.cumsum(first) - 1
        return jax.ops.segment_sum(g, run, num_segments=g.shape[0],
                                   indices_are_sorted=True)[run]

    return keys, jax.lax.cond(jnp.all(first | (keys == scratch)),
                              lambda g: g, summed, grad)


def _make_programs(alpha: float, beta: float, lambda1: float,
                   lambda2: float, scratch: int):
    """The table's two device programs. ``ids`` is an op's bucket of keys
    (``DeviceIdsServer.launch_ids``), ``live`` the slots of it the program
    works on (static). An Add's ``rows`` (static): None where XLA gathers,
    steps and scatters, else the row kernel reads, steps and writes the
    keys' rows (``pallas_rows.add_at_lanes`` under :func:`ftrl_step`),
    interpreted (True) or compiled. An Add returns ``(z, n, rows walked)``:
    the count of distinct rows the kernel read and wrote, an int32 on the
    device, None from XLA's path."""

    step = functools.partial(ftrl_step, alpha=alpha, beta=beta,
                             lambda1=lambda1, lambda2=lambda2)

    def _ftrl_keyed_get(z, n, ids, live):
        at = ids[:live]
        w = ftrl_weights(state_of_slots(z, at), state_of_slots(n, at),
                         alpha, beta, lambda1, lambda2)
        tail = ids.shape[0] - live
        if not tail:
            return w
        # the slots past the keys gathered: the scratch key's weight, read
        # once (the shape follows the bucket alone)
        rest = ftrl_weights(z[scratch], n[scratch], alpha, beta, lambda1,
                            lambda2)
        return jnp.concatenate([w, jnp.broadcast_to(rest, (tail,))])

    def _ftrl_keyed_add(z, n, ids, grad, live, rows=None):
        at = ids[:live]
        have = grad.shape[0]
        g = grad[:live] if have >= live else jnp.concatenate(
            [grad, jnp.zeros(live - have, grad.dtype)])
        # a slot aimed at the scratch key steps nothing, whatever the
        # caller's buffer holds past its keys
        at, g = _summed_by_key(at, jnp.where(at == scratch, 0.0, g), scratch)
        if rows is None:
            # XLA's gathers, the rule a slot, XLA's scatters: slots of one
            # key (a repeated key, the scratch slots) write the value they
            # all computed
            z_new, n_new = step(state_of_slots(z, at), state_of_slots(n, at),
                                g)
            return (z.at[at].set(z_new, indices_are_sorted=True),
                    n.at[at].set(n_new, indices_are_sorted=True), None)
        # the distinct rows of 128 the keys live in, read once each,
        # stepped where they landed in VMEM and written back by the row
        # kernel: each key's lane takes the rule, once (the kernel steps a
        # repeated key at its first slot), and this program gathers no
        # state. How many rows that was is its third result
        (z, n), walked = _row_kernel().add_at_lanes(
            (z, n), at, (g,), at != scratch, interpret=rows,
            step=lambda state, brought: step(*state, *brought))
        return z, n, walked

    # named so that the compiled modules are `jit__ftrl_keyed_get` and
    # `jit__ftrl_keyed_add` in a trace
    return (jax.jit(_ftrl_keyed_get, static_argnames=("live",)),
            jax.jit(_ftrl_keyed_add, static_argnames=("live", "rows"),
                    donate_argnums=(0, 1)))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_piece(z, n, z_piece, n_piece, at):
    """``(z, n)`` with a piece of each written at key ``at``, in place."""
    return (jax.lax.dynamic_update_slice(z, z_piece, (at,)),
            jax.lax.dynamic_update_slice(n, n_piece, (at,)))


class FTRLServer(DeviceIdsServer, ServerTable):
    """``init``: None for a state of zeros (made on the device), or a block
    source ``(lo, count) -> (z, n)`` of keys ``[lo, lo + count)``, numpy or
    device arrays, asked in key order for at most ``_PIECE_KEYS`` keys at a
    time and written into the state piece by piece: the host never holds an
    array of the key space."""

    def __init__(self, size: int, alpha: float = 0.1, beta: float = 1.0,
                 lambda1: float = 1.0, lambda2: float = 1.0,
                 init: Optional[Callable[[int, int], Tuple[Any, Any]]] = None
                 ) -> None:
        super().__init__()
        zoo = Zoo.instance()
        self.size = int(size)
        self.alpha, self.beta = float(alpha), float(beta)
        self.lambda1, self.lambda2 = float(lambda1), float(lambda2)
        self.mesh = zoo.mesh
        num_shards = zoo.num_servers
        # the scratch key padding aims at, and whole lane tiles on every
        # shard (`state_of_slots` reads the state as rows of 128)
        self.scratch_key = self.size
        self.padded = mesh_lib.pad_to_multiple(self.size + 1,
                                               1024 * num_shards)
        self._sharding = mesh_lib.table_sharding(self.mesh, ndim=1)
        # which program writes an Add back, chosen once from the mesh and
        # the platform (a launch adds its bucket), around the fill of the
        # state, under which the row kernel's module loads
        self.plan = row_plan(
            self.mesh, zoo.multihost is not None,
            keyed=_make_programs(self.alpha, self.beta, self.lambda1,
                                 self.lambda2, self.scratch_key),
            fill=functools.partial(self._make_state, init))
        self._init_device_ids(self.scratch_key, num_shards == 1)
        self._keys_get = Dashboard.counter("FTRL_KEYS_GET")
        self._keys_add = Dashboard.counter("FTRL_KEYS_ADD")
        # the ops that came from a remote client (`RemoteServer._handle`)
        self.served_over_wire = {
            MsgType.Request_Get: Dashboard.counter("FTRL_SERVED_GET"),
            MsgType.Request_Add: Dashboard.counter("FTRL_SERVED_ADD")}
        log.info("FTRLTable %d keys (z, n: %d B) on %d %s device(s): keyed "
                 "Get and Add, a Get by XLA gather, %s", self.size,
                 8 * self.padded, num_shards,
                 self.mesh.devices.flat[0].platform, self.plan.why)

    def _make_state(self, source=None) -> None:
        """``z`` and ``n`` as zeros made on the device, then ``source``'s
        blocks written into them piece by piece."""
        self.z = jnp.zeros(self.padded, jnp.float32, device=self._sharding)
        self.n = jnp.zeros(self.padded, jnp.float32, device=self._sharding)
        if source is None:
            return
        for lo in range(0, self.size, _PIECE_KEYS):
            count = min(_PIECE_KEYS, self.size - lo)
            z, n = source(lo, count)
            z, n = jnp.asarray(z, jnp.float32), jnp.asarray(n, jnp.float32)
            if z.shape != (count,) or n.shape != (count,):
                log.fatal("FTRLTable: the block source gave %s and %s for "
                          "%d keys", z.shape, n.shape, count)
            self.z, self.n = _write_piece(self.z, self.n, z, n,
                                          jnp.int32(lo))
            # one piece on the device at a time (a source that makes its
            # pieces there would else have them all in flight)
            self.z.block_until_ready()

    # -- the ids' form -------------------------------------------------------
    def launch_form(self, n: int, op: str, ensure_pad: bool = False,
                    rows: Optional[int] = None) -> Tuple[int, bool]:
        """One form whatever the op and its gradient's length, so that a
        Get's and an Add's of the same keys are one array: the next power
        of two with at least one scratch slot, and no slot that holds a
        count (the Add's program masks the scratch slots itself)."""
        return max(next_pow2(n + 1), _MIN_BUCKET), False

    # -- server ops ------------------------------------------------------------
    def _keys_of(self, keys, op: str):
        """``(keys int32, what the caller sent up or None)`` of a request's
        keys; None names every key."""
        took = getattr(keys, "took", None)
        if keys is None:
            keys = np.arange(self.size, dtype=np.int32)
        keys = np.asarray(keys, np.int32).reshape(-1)
        if took is None and keys.size and (
                int(keys.min()) < 0 or int(keys.max()) >= self.size):
            log.fatal("FTRLTable.%s: key out of range [0, %d)", op, self.size)
        return keys, took

    def process_add(self, request) -> None:
        with span("TABLE_PROCESS_ADD"):
            keys, grad = request
            keys, took = self._keys_of(keys, "add")
            n = len(keys)
            if not isinstance(grad, jax.Array):
                grad = np.asarray(grad, np.float32).reshape(-1)
            elif grad.ndim != 1 or grad.dtype != jnp.float32:
                grad = grad.reshape(-1).astype(jnp.float32)
            if grad.shape[0] < n:
                log.fatal("FTRLTable.add: %d keys but %d gradient values",
                          n, grad.shape[0])
            took, ids_from = self.plan.took_ids(self, keys, "add", took)
            live = live_slots(n, took.bucket)
            if isinstance(grad, jax.Array):
                grad = self.plan.device_delta(grad, took.bucket)
            else:
                # a host gradient goes up at the slots its program works
                # on, zeros past its keys: ONE program for every count of
                # keys under those slots, as for a Get, not one a count
                # (a served trainer's minibatches each name another count)
                padded = np.zeros(live, np.float32)
                padded[:n] = grad[:n]
                grad = async_upload(padded)
            self.z, self.n = self.plan.launch_add(
                (self.z, self.n), took, grad, live, ids_from)
            self._keys_add.add(n)

    def process_get(self, request):
        return PendingHostRead.fetched(self.launch_get(request))

    def launch_get(self, request):
        with span("TABLE_PROCESS_GET"):
            keys, device_out = request
            keys, took = self._keys_of(keys, "get")
            # (bucket,): the weights of the keys named, then the scratch
            # key's
            w = self.plan.launch_get(self, (self.z, self.n), keys, took,
                                     device_out)
            self._keys_get.add(len(keys))
            if device_out:
                return w
            # launched; fetched by whoever finishes the Get
            return self._host_read_behind(w, slice(len(keys)))

    def remote_spec(self):
        return {"kind": "ftrl", "size": self.size}

    # -- checkpoint: two 1-D states, each fetched whole (3.5 GB at the
    # benchmark's key space: PERF.md section 7) -------------------------------
    def store(self, stream) -> None:
        from multiverso_tpu.checkpoint import write_array
        write_array(stream, self._host_read(self.z)[: self.size])
        write_array(stream, self._host_read(self.n)[: self.size])

    def load(self, stream) -> None:
        from multiverso_tpu.checkpoint import read_array
        state = [read_array(stream).astype(np.float32).reshape(-1)
                 for _ in range(2)]
        if any(len(s) != self.size for s in state):
            log.fatal("FTRLTable.load: a state of %d keys for a table of %d",
                      len(state[0]), self.size)
        self._make_state(lambda lo, count: (state[0][lo:lo + count],
                                            state[1][lo:lo + count]))


class FTRLWorker(DeviceIdsWorker, WorkerTable):
    """Client proxy: an Add ships raw gradients, a Get returns the weights
    derived from ``(z, n)``; by key, or (no keys) over the whole table."""

    supports_device_io = True

    def __init__(self, size: int, alpha: float = 0.1, beta: float = 1.0,
                 lambda1: float = 1.0, lambda2: float = 1.0,
                 init: Optional[Callable] = None,
                 server: Optional[FTRLServer] = None) -> None:
        super().__init__()
        self._waited = threading.local()
        self.size = int(size)
        self._server_table = server or FTRLServer(size, alpha, beta,
                                                  lambda1, lambda2, init)
        self._register(self._server_table)
        if Zoo.instance().multihost is not None:
            # lockstep descriptors must be host-serializable
            self.supports_device_io = False

    @property
    def scratch_key(self) -> int:
        return self._server_table.scratch_key

    # -- the order of the Adds ----------------------------------------------
    def wait(self, msg_id: int) -> Any:
        """``WorkerTable.wait``; the op's Add ordinal is then
        ``last_ordinal``."""
        completion = self._pending.get(msg_id)
        result = super().wait(msg_id)
        self._waited.ordinal = completion.ordinal
        return result

    @property
    def last_ordinal(self) -> Optional[int]:
        """The table's Add ordinal that the async server stamped on the op
        the calling thread last waited for on this proxy (``wait``, or the
        ``get`` / ``add`` that wait themselves). An Add's: its own place,
        1, 2, ..., in the ONE order in which the server applied the Adds of
        every worker to this table; a retried Add keeps the place of its
        one application. A Get's: how many Adds had been applied when it
        was launched; the weights it returns are the state after exactly
        those. None before any op, and under a server that stamps nothing
        (the round-gated and the deterministic servers: their rounds are
        the order). It orders the Adds of one table of one serving
        process, for as long as that process lives."""
        return getattr(self._waited, "ordinal", None)

    def _keys(self, keys, submit=None) -> Optional[np.ndarray]:
        """A request's keys: int32, inside the table; None is every key."""
        if keys is None:
            return None
        keys = np.asarray(keys, np.int32).reshape(-1)
        if submit is not None:
            submit.n = len(keys)
        if keys.size and (keys.min() < 0 or keys.max() >= self.size):
            log.fatal("FTRL key out of range [0, %d)", self.size)
        return keys

    # -- host forms: numpy in, numpy out, the same two programs -----------------
    def get(self, keys: Optional[np.ndarray] = None) -> np.ndarray:
        """The weights of ``keys`` (every key's without)."""
        with self._public_op():
            return super().get((self._keys(keys), False))

    def get_async(self, keys: Optional[np.ndarray] = None) -> int:
        with self._public_op(), span("WORKER_SUBMIT") as submit:
            return self._submit(MsgType.Request_Get,
                                (self._keys(keys, submit), False), submit)

    @staticmethod
    def _host_add(keys, grads):
        """``add(grad)`` is the whole-array form, ``add(keys, grads)`` the
        keyed one."""
        return (None, keys) if grads is None else (keys, grads)

    def add(self, keys, grads: Optional[np.ndarray] = None) -> None:
        """One FTRL step of ``keys`` from their raw gradients ``grads``;
        ``add(grad)``, a gradient for every key, steps the whole table. A
        key named twice takes ONE step from the sum of its gradients."""
        with self._public_op():
            keys, grads = self._host_add(keys, grads)
            super().add((self._keys(keys), grads))

    def add_async(self, keys, grads: Optional[np.ndarray] = None) -> int:
        with self._public_op(), span("WORKER_SUBMIT") as submit:
            keys, grads = self._host_add(keys, grads)
            return self._submit(MsgType.Request_Add,
                                (self._keys(keys, submit), grads), submit)

    # -- device IO (in-process workers only) ------------------------------------
    def get_device_async(self, keys: np.ndarray) -> int:
        """Async pull that stays in HBM: the reply (``wait_device``) is a
        ``(bucket,)`` float32 jax.Array, the bucket the next power of two
        above ``len(keys)``, whose slots past the keys hold the scratch
        key's weight. On a table on one device the keys are copied and
        their upload begins here, on the caller's thread
        (``DeviceIdsWorker._ids_at_submit``); an op that names the keys of
        this proxy's last device-path op sends nothing up."""
        self._require_device_io()
        with span("WORKER_SUBMIT") as submit:
            keys = self._keys(keys, submit)
            return self._submit(
                MsgType.Request_Get,
                (self._ids_at_submit(keys, "get"), True), submit)

    def wait_device(self, msg_id: int) -> "jax.Array":
        return self.wait(msg_id)

    def add_device_async(self, grads: "jax.Array", keys: np.ndarray) -> int:
        """Async device-resident Add of the raw gradients ``grads``, a
        float32 jax.Array of ``len(keys)`` values or MORE (a buffer held at
        the Get's bucket: its values past the keys step nothing, and one
        program serves every count of keys under a bucket). The keys go up
        as a Get's do, so a push of the keys just pulled launches on the
        array the pull left on the device. A key named twice takes ONE step
        from the sum of its gradients."""
        self._require_device_io()
        with span("WORKER_SUBMIT") as submit:
            keys = self._keys(keys, submit)
            return self._submit(
                MsgType.Request_Add,
                (self._ids_at_submit(keys, "add", grads.shape[0]), grads),
                submit)

    # -- the state as the server holds it ---------------------------------------
    def get_state_device(self, name: str) -> jax.Array:
        """``z`` or ``n`` on the device: the keys, then scratch entries."""
        return getattr(self._server_table, name)
