"""Server-side optimizers ("updaters") applied inside ProcessAdd.

Reference capability (not copied): ``Updater<T>::Update/Access`` + factory
``GetUpdater`` keyed on the ``updater_type`` flag, with ``AddOption``/
``GetOption`` per-request hyperparameter envelopes riding each message
(``include/multiverso/updater/updater.h:10-132``, ``src/updater/updater.cpp``);
concrete updaters: default (+=), SGD (-=), momentum EMA, per-worker AdaGrad
(``include/multiverso/updater/{sgd,momentum,adagrad}_updater.h``), and a
declared-but-absent DCASGD slot (``CMakeLists.txt:9``).

TPU-native re-design: an updater is a *pure function* ``apply(data, states,
delta, option) -> (data, states)`` over same-shape slices, jitted and donated
by the owning table, so the whole-table and row-subset paths share one
compiled update. Optimizer state lives in HBM sharded exactly like the table.
Every state array carries a leading worker dimension (1 when the optimizer is
worker-agnostic) so per-worker state (AdaGrad, DCASGD) and shared state
(momentum) flow through the same table machinery. Known reference bug NOT
reproduced: AdaGrad accumulator was read via a copy and never persisted
(``adagrad_updater.h:26``); here states round-trip through the jitted call.

DCASGD is fully implemented (the reference only reserved the option): the
delay-compensated ASGD rule ``data -= lr*(g + lambda * g*g*(data - backup))``
with a per-worker backup of parameters at last read.

Row-wise AdaGrad (``rowwise_adagrad``) is the one updater whose state is not
shaped like the table: one float32 a row, shared by the workers
(``row_state``). Its rule splits into a state step over the delta alone and
a plain scaled Add, so a matrix table keeps it on the row kernel.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax.numpy as jnp
import numpy as np

from multiverso_tpu import config, log


@dataclass
class AddOption:
    """Per-request hyperparameters riding an Add (wire-compatible 5-field
    envelope: worker_id, momentum, learning_rate, rho, lambda)."""

    worker_id: int = 0
    momentum: float = 0.0
    learning_rate: float = 0.1
    rho: float = 0.1
    lambda_: float = 1.0

    _WIRE = struct.Struct("<i4f")

    def to_bytes(self) -> bytes:
        return self._WIRE.pack(self.worker_id, self.momentum,
                               self.learning_rate, self.rho, self.lambda_)

    @classmethod
    def from_bytes(cls, raw: bytes) -> "AddOption":
        w, m, lr, rho, lam = cls._WIRE.unpack(raw[:cls._WIRE.size])
        return cls(w, m, lr, rho, lam)

    def scalars(self) -> Tuple[float, float, float, float]:
        return (self.momentum, self.learning_rate, self.rho, self.lambda_)


@dataclass
class GetOption:
    worker_id: int = 0

    _WIRE = struct.Struct("<i")

    def to_bytes(self) -> bytes:
        return self._WIRE.pack(self.worker_id)

    @classmethod
    def from_bytes(cls, raw: bytes) -> "GetOption":
        (w,) = cls._WIRE.unpack(raw[:cls._WIRE.size])
        return cls(w)


class Updater:
    """Base updater. Subclasses override ``apply`` (and ``state_spec`` when
    they carry optimizer state).

    ``data``: slice of table values (any shape). ``states``: dict of state
    slices, each shaped like ``data`` (already sliced to the acting worker).
    ``option_scalars``: (momentum, lr, rho, lambda) as traced scalars.
    """

    name = "default"
    per_worker_state = False
    # True: every state is one value a ROW, ``(rows,)``, shared by the
    # workers, with no worker dimension; the updater gives ``row_step`` in
    # place of ``apply`` and serves matrix tables only
    row_state = False

    def check_option(self, option: AddOption) -> None:
        """Raises for an Add's option this updater cannot mean (a table
        calls it before every Add; the waiter gets the error)."""

    def state_spec(self, table_shape: Tuple[int, ...],
                   dtype: Any) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape-suffix, dtype); actual arrays get a leading worker dim."""
        return {}

    def apply(self, data, states: Dict[str, Any], delta,
              option_scalars) -> Tuple[Any, Dict[str, Any]]:
        return data + delta, states

    def access(self, data):
        """Transform on Get (reference ``Updater::Access``); default identity."""
        return data


class SGDUpdater(Updater):
    """``data -= delta`` — delta pre-scaled by the caller."""

    name = "sgd"

    def apply(self, data, states, delta, option_scalars):
        return data - delta, states


class MomentumUpdater(Updater):
    """EMA smoothing: ``smooth = m*smooth + (1-m)*delta; data -= smooth``."""

    name = "momentum_sgd"

    def state_spec(self, table_shape, dtype):
        return {"smooth": (table_shape, dtype)}

    def apply(self, data, states, delta, option_scalars):
        m = option_scalars[0]
        smooth = m * states["smooth"] + (1.0 - m) * delta
        return data - smooth, {"smooth": smooth}


class AdaGradUpdater(Updater):
    """Per-worker historic squared-gradient accumulators:
    ``g_sqr += delta²; data -= lr * delta / sqrt(g_sqr + rho)``."""

    name = "adagrad"
    per_worker_state = True

    def state_spec(self, table_shape, dtype):
        return {"g_sqr": (table_shape, jnp.float32)}

    def apply(self, data, states, delta, option_scalars):
        lr, rho = option_scalars[1], option_scalars[2]
        g_sqr = states["g_sqr"] + jnp.square(delta).astype(jnp.float32)
        step = lr * delta / jnp.sqrt(g_sqr + rho).astype(delta.dtype)
        return data - step, {"g_sqr": g_sqr}


class DCASGDUpdater(Updater):
    """Delay-compensated ASGD: compensates gradient staleness with the
    diagonal Hessian approximation ``g ⊙ g ⊙ (data - backup)`` where
    ``backup`` is the per-worker parameter snapshot at last Get."""

    name = "dcasgd"
    per_worker_state = True

    def state_spec(self, table_shape, dtype):
        return {"backup": (table_shape, dtype)}

    def apply(self, data, states, delta, option_scalars):
        lr, lam = option_scalars[1], option_scalars[3]
        backup = states["backup"]
        comp = delta + lam * delta * delta * (data - backup)
        new_data = data - lr * comp
        return new_data, {"backup": new_data}


class RowwiseAdaGradUpdater(Updater):
    """Row-wise sparse AdaGrad: one float32 of state a row. For every row
    ``r`` an Add names, ``g_r`` its raw gradient (``cols`` values)::

        s_r <- s_r + mean_j(g_rj^2)                # initial 0
        w_r <- w_r - lr * g_r / (sqrt(s_r) + eps)  # eps outside the root

    Source: ``facebookresearch/dlrm``, ``dlrm_s_pytorch.py
    --optimizer=rwsadagrad`` (``optim/rwsadagrad.py``, ``RWSAdagrad``);
    FBGEMM's ``EXACT_ROWWISE_ADAGRAD`` is the same arithmetic. ``lr`` and
    ``eps`` ride the Add's option as ``learning_rate`` and ``rho`` (the
    source's defaults are 0.01 and 1e-10). The option's own defaults are
    0.1 and 0.1, and an ``eps`` of 0.1 is a billion times the source's: a
    table refuses an Add that brings no option, or one whose ``rho`` is
    left at that default (``check_option``), so a trainer names both. One
    departure: the source's
    ``lr_decay``, ``weight_decay`` and ``initial_accumulator_value`` are
    left at their defaults of 0 and have no option here.

    Rows an Add does not name keep ``w`` and ``s`` to the last bit. The
    state is shared, not per worker: Adds are optimizer steps in the order
    the server acknowledges them, so two Adds that name one row do not
    commute and are never summed into one (a table fuses no host Adds
    under this updater). Within one Add the ids are distinct (a worker
    sums its own duplicates; the host path does it for a request that did
    not).

    The rule splits: ``s`` depends on the delta alone, and with ``s_r``
    known the table's update is a plain Add of
    ``-(lr / (sqrt(s_r) + eps)) * g_r``. ``row_step`` is that split; the
    table gathers and writes the named rows' ``s`` and hands the scaled
    delta to the row scatter-add its linear updaters use."""

    name = "rowwise_adagrad"
    row_state = True

    def state_spec(self, table_shape, dtype):
        return {"s": (tuple(table_shape[:1]), jnp.float32)}

    def check_option(self, option: AddOption) -> None:
        if np.float32(option.rho) == np.float32(AddOption.rho):
            raise ValueError(
                "rowwise_adagrad: the Add's option leaves rho, the rule's "
                "eps, at the option's default %g; name learning_rate and "
                "rho (the source's are 0.01 and 1e-10)" % AddOption.rho)

    def row_step(self, states, delta, option_scalars, cols: int):
        """``(increment, new states)`` for rows whose states are
        ``states`` (``(n,)`` each) and whose gradients are ``delta``
        (``(n, <=cols)``; columns it lacks, and lanes past ``cols``, are
        zeros): the table adds ``increment`` to those rows."""
        lr, eps = option_scalars[1], option_scalars[2]
        g = delta.astype(jnp.float32)
        # times the reciprocal: exact where ``cols`` is a power of two,
        # which a device's division need not be
        s = states["s"] + jnp.sum(g * g, axis=1) * (1.0 / cols)
        step = (-lr / (jnp.sqrt(s) + eps))[:, None] * g
        return step.astype(delta.dtype), {"s": s}

    def apply(self, data, states, delta, option_scalars):
        raise NotImplementedError(
            "rowwise_adagrad has no same-shape state: tables call row_step")


_REGISTRY: Dict[str, Callable[[], Updater]] = {
    "default": Updater,
    "sgd": SGDUpdater,
    "momentum_sgd": MomentumUpdater,
    "adagrad": AdaGradUpdater,
    "dcasgd": DCASGDUpdater,
    "rowwise_adagrad": RowwiseAdaGradUpdater,
}


def register_updater(name: str, factory: Callable[[], Updater]) -> None:
    """Open extension point (the reference's factory was a closed switch)."""
    _REGISTRY[name] = factory


def get_updater(dtype: Any, updater_type: str = "") -> Updater:
    """Factory keyed on the ``updater_type`` flag. Integer tables always get
    the plain accumulating updater (reference behavior)."""
    if np.issubdtype(np.dtype(dtype), np.integer):
        return Updater()
    name = updater_type or config.get_flag("updater_type")
    factory = _REGISTRY.get(name)
    if factory is None:
        log.fatal("unknown updater_type: %s", name)
    return factory()
