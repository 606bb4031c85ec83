"""Model parameter managers: a whole model's params in ONE ArrayTable.

Parity with ``binding/python/multiverso/theano_ext/param_manager.py:9-90``
(``MVModelParamManager``) and its lasagne/keras subclasses: flatten every
parameter into a single 1-D table; ``sync_all_param()`` pushes the delta
since the last sync and writes the merged global value back into the model.

TPU-era managers:

* :class:`PytreeParamManager` — any JAX pytree of arrays (flax ``params``
  dicts, haiku params, optax states). Pytrees are immutable, so the manager
  owns the current tree (``.params``) and ``sync()`` returns the merged one.
* :class:`TorchParamManager` — a ``torch.nn.Module`` (parity with the
  Torch-Lua binding's per-parameter handlers, ``binding/lua/``, and the
  keras manager's get/set-weights shape).
"""

from __future__ import annotations

from typing import Any, List, Sequence

import numpy as np

import multiverso_tpu as mv


def admin_seed(table, flat=None):
    """Master-seed a freshly created table and read its settled value, all
    as ADMINISTRATIVE (un-clocked) traffic. Setup must not be charged to a
    worker's round budget: under BSP an unbound thread defaults to slot 0
    and a gated Get would wedge the round gate before training starts.
    Master-ness is decided BEFORE entering admin (inside, the thread has
    no worker identity at all). ``flat=None`` skips the seeding add (the
    table already carries state)."""
    from multiverso_tpu.runtime.zoo import Zoo
    zoo = Zoo.instance()
    is_master = mv.is_master_worker()
    with zoo.admin():
        if flat is not None and is_master:
            table.add(flat)
        # seed must be visible before the first pull; process-level barrier
        # (a per-worker mv.barrier() would deadlock single-caller setup)
        zoo.process_barrier()
        return table.get()


class ParamManager:
    """Base manager. Subclasses implement :meth:`get_all_param_values` /
    :meth:`set_all_param_values` over lists of numpy arrays
    (``param_manager.py:43-59`` contract)."""

    def __init__(self) -> None:
        values = self.get_all_param_values()
        self._shapes = [v.shape for v in values]
        self._dtypes = [v.dtype for v in values]
        self._sizes = [int(v.size) for v in values]
        flat = np.concatenate(
            [np.asarray(v, dtype=np.float32).reshape(-1) for v in values]
        ) if values else np.zeros(0, np.float32)
        # master-only Add into a zero table: shard-consistent under
        # multi-process SPMD (see sharedvar.py seeding note)
        self._table = mv.create_table("array", flat.size, np.float32)
        self._last_synced = admin_seed(self._table, flat)
        self._set_from_flat(self._last_synced)

    # -- subclass surface ---------------------------------------------------
    def get_all_param_values(self) -> List[np.ndarray]:
        raise NotImplementedError

    def set_all_param_values(self, values: Sequence[np.ndarray]) -> None:
        raise NotImplementedError

    # -- internals ----------------------------------------------------------
    def _flat(self) -> np.ndarray:
        values = self.get_all_param_values()
        if not values:
            return np.zeros(0, np.float32)
        return np.concatenate(
            [np.asarray(v, dtype=np.float32).reshape(-1) for v in values])

    def _set_from_flat(self, flat: np.ndarray) -> None:
        out, n = [], 0
        for shape, dtype, size in zip(self._shapes, self._dtypes, self._sizes):
            out.append(flat[n:n + size].reshape(shape).astype(dtype))
            n += size
        self.set_all_param_values(out)

    @property
    def table(self):
        return self._table

    # -- API ----------------------------------------------------------------
    def sync_all_param(self) -> None:
        """Push local delta, pull merged params, write back into the model
        (``param_manager.py:70-83``)."""
        current = self._flat()
        self._table.add(current - self._last_synced)
        self._last_synced = self._table.get()
        self._set_from_flat(self._last_synced)

    sync = sync_all_param


class PytreeParamManager(ParamManager):
    """Manage a JAX pytree of arrays (flax/haiku/optax)."""

    def __init__(self, params: Any) -> None:
        import jax
        self._jax = jax
        self._leaves, self._treedef = jax.tree_util.tree_flatten(params)
        super().__init__()

    @property
    def params(self) -> Any:
        return self._jax.tree_util.tree_unflatten(self._treedef, self._leaves)

    @params.setter
    def params(self, tree: Any) -> None:
        leaves, treedef = self._jax.tree_util.tree_flatten(tree)
        if treedef != self._treedef:
            mv.log.fatal("pytree structure changed across sync")
        self._leaves = leaves

    def get_all_param_values(self) -> List[np.ndarray]:
        return [np.asarray(leaf) for leaf in self._leaves]

    def set_all_param_values(self, values: Sequence[np.ndarray]) -> None:
        import jax.numpy as jnp
        self._leaves = [jnp.asarray(v) for v in values]

    def sync(self, params: Any = None) -> Any:
        """Functional spelling: ``params = manager.sync(params)``."""
        if params is not None:
            self.params = params
        self.sync_all_param()
        return self.params

    sync_all_param = ParamManager.sync_all_param

    def worker_view(self, device: bool = False) -> "PytreeWorkerSync":
        """Per-worker syncer over this manager's SHARED table. Each view
        owns its own last-synced baseline, which is the reference's actual
        topology — every process tracked its own delta base
        (``param_manager.py:70-83`` ran once per process). Sharing one
        manager between threads instead makes worker A's push subtract
        worker B's freshly-merged work (their baselines alias). Views
        need no lock: table add/get are dispatcher-serialized.

        ``device=True`` keeps the whole sync in HBM (jitted flatten/split +
        the table's device add/get): no host copy of the model per sync —
        the TPU-era replacement for the reference's host-side serialize
        path."""
        return PytreeWorkerSync(self, device=device)


class PytreeWorkerSync:
    """See :meth:`PytreeParamManager.worker_view`. Starts from the current
    global table value; ``sync(tree)`` pushes this worker's delta and
    returns the merged global tree."""

    def __init__(self, manager: "PytreeParamManager",
                 device: bool = False) -> None:
        from multiverso_tpu.runtime.zoo import Zoo
        self._jax = manager._jax
        self._treedef = manager._treedef
        self._shapes = manager._shapes
        self._dtypes = manager._dtypes
        self._sizes = manager._sizes
        self._table = manager.table
        self._zoo = Zoo.instance()
        # pipelined-sync state (sync_pipelined/drain): the outstanding
        # push's handle, and the baseline matching what the caller is
        # currently computing FROM (one reply behind _last)
        self._inflight = None
        self._last_handed = None
        self._device = bool(device) and getattr(
            self._table, "supports_device_io", False)
        if self._device:
            jax = self._jax

            import jax.numpy as jnp_mod

            @jax.jit
            def copy_fn(ls):
                return [jnp_mod.copy(x) for x in ls]

            self._copy_fn = copy_fn
            # _last is a list of SINGLE-DEVICE leaves (the server's leaf
            # codec commits them): worker-thread math on them never runs
            # cross-shard collectives, which must stay on the dispatcher
            template = [jax.numpy.zeros(s, d)
                        for s, d in zip(self._shapes, self._dtypes)]
            with self._zoo.admin():  # setup read: un-clocked
                self._last = self._table.wait(
                    self._table.get_leaves_async(template))
        else:
            with self._zoo.admin():
                self._last = self._table.get()

    def _unflatten(self, flat) -> Any:
        if self._device:
            return self._jax.tree_util.tree_unflatten(self._treedef,
                                                      list(flat))
        import jax.numpy as jnp
        leaves, n = [], 0
        for shape, dtype, size in zip(self._shapes, self._dtypes,
                                      self._sizes):
            leaves.append(jnp.asarray(
                flat[n:n + size].reshape(shape).astype(dtype)))
            n += size
        return self._jax.tree_util.tree_unflatten(self._treedef, leaves)

    @property
    def params(self) -> Any:
        if self._inflight is not None:
            mv.log.fatal("a pipelined sync is outstanding; call drain() "
                         "before reading params")
        if self._device:  # hand out copies; callers may donate them
            return self._unflatten(self._copy_fn(self._last))
        return self._unflatten(self._last)

    def sync(self, tree: Any) -> Any:
        leaves, treedef = self._jax.tree_util.tree_flatten(tree)
        if treedef != self._treedef:
            mv.log.fatal("pytree structure changed across sync")
        if self._device:
            last = self._last
            if self._inflight is not None:
                # mixing after sync_pipelined: consume the outstanding
                # reply, but the delta base for THIS push must stay the
                # value the caller computed FROM (_last_handed) — rebasing
                # onto the drained merged value would subtract peers'
                # (and our own in-flight) work from the delta
                self._table.wait(self._inflight)
                self._inflight = None
                last = self._last_handed
                self._last_handed = None
            server = self._zoo.server
            if not getattr(server, "plain_async", False):
                # BSP (fused reply samples at apply time — cannot honor
                # the round-gated Get contract) or deferred-apply
                # (deterministic: fused reply would be None): reply-free
                # pair push, then a properly gated/ordered get
                self._table.wait(
                    self._table.push_leaves_async(leaves, last))
                merged = self._table.wait(
                    self._table.get_leaves_async(leaves))
                # baseline keeps its OWN buffers: the caller typically
                # feeds the returned tree into a donating train step,
                # which would delete a shared _last out from under the
                # next delta
                self._last = self._copy_fn(merged)
                return self._unflatten(merged)
            # HBM end-to-end, ONE device dispatch for the whole sync: the
            # server computes new-last, applies the update, and replies
            # (merged, baseline) from a single fused jit — every dispatch
            # has a fixed host submission cost (not measured on the current
            # machine), and this path submits exactly one
            merged, self._last = self._table.wait(
                self._table.sync_leaves_async(leaves, last_leaves=last))
            return self._unflatten(merged)
        flat = np.concatenate(
            [np.asarray(l, dtype=np.float32).reshape(-1) for l in leaves]
        ) if leaves else np.zeros(0, np.float32)
        self._table.add(flat - self._last)
        self._last = self._table.get()
        return self._unflatten(self._last)

    def sync_pipelined(self, tree: Any) -> Any:
        """One-round-stale sync that never blocks on the server: submits
        this round's push and returns the PREVIOUS round's merged value
        (the reference's double-buffer prefetch shape,
        ``ps_model.cpp:236-271``, applied to ASGD). The returned tree is
        one round stale; the local delta is never lost — it is in flight.

        Delta bookkeeping needs TWO baselines: the push's ``last`` must be
        the value the worker actually computed FROM (the tree handed out
        two calls ago), not the latest merged value — using the latest
        would subtract the worker's own in-flight push from its next
        delta. Falls back to blocking :meth:`sync` on servers that gate
        or defer (BSP/deterministic), where rounds cannot overlap."""
        if not self._device or not getattr(self._zoo.server,
                                           "plain_async", False):
            return self.sync(tree)
        leaves, treedef = self._jax.tree_util.tree_flatten(tree)
        if treedef != self._treedef:
            mv.log.fatal("pytree structure changed across sync")
        handed = self._last_handed
        first = handed is None
        merged_prev = baseline_prev = None
        if first:
            handed = self._last  # view init value: the caller's start point
            # first call hands back the init value; the push is in flight.
            # Two SEPARATE copies (merged_prev gets donated by the caller's
            # train step; baseline_prev must survive as the next push's
            # donated last_leaves), submitted BEFORE the push so they read
            # `handed` ahead of the fused sync donating it.
            merged_prev = self._copy_fn(handed)
            baseline_prev = self._copy_fn(handed)
            self._last = None  # donated by the push below
        handle = self._table.sync_leaves_async(leaves, last_leaves=handed)
        if not first:
            # the async Server never replies None (gated/deferred servers
            # were routed to sync() above and cannot change mid-run)
            merged_prev, baseline_prev = self._table.wait(self._inflight)
        self._inflight = handle
        self._last_handed = baseline_prev
        return self._unflatten(merged_prev)

    def drain(self) -> Any:
        """Complete an outstanding :meth:`sync_pipelined` push and return
        the up-to-date merged tree (call once after the training loop)."""
        inflight = self._inflight
        if inflight is None:
            return self.params
        # sync_pipelined only leaves _inflight set on the plain async
        # Server, whose pair-sync reply is never None
        merged, self._last = self._table.wait(inflight)
        self._inflight = None
        self._last_handed = None
        return self._unflatten(merged)


class TorchParamManager(ParamManager):
    """Manage a ``torch.nn.Module``'s parameters."""

    def __init__(self, module: Any) -> None:
        self._module = module
        super().__init__()

    @property
    def module(self) -> Any:
        return self._module

    def get_all_param_values(self) -> List[np.ndarray]:
        return [p.detach().cpu().numpy() for p in self._module.parameters()]

    def set_all_param_values(self, values: Sequence[np.ndarray]) -> None:
        import torch
        with torch.no_grad():
            for p, v in zip(self._module.parameters(), values):
                p.copy_(torch.from_numpy(np.ascontiguousarray(v)))
