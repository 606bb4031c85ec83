"""Logistic / softmax / FTRL regression — the second reference application,
rebuilt TPU-first.

Reference capability (not copied): the LogisticRegression app — linear /
sigmoid / softmax / FTRL objectives, L1/L2 regularizers, dense or sparse
features, local or parameter-server mode with sync-frequency pulls and a
double-buffered pipeline, plus custom user tables
(``Applications/LogisticRegression/src/``: logreg.cpp, model/, objective/,
regular/, updater/).

TPU-native re-design: one jitted train step per objective (dense einsum or
padded-sparse gather/segment-dot on device); sparse minibatches are
static-shape (B, max_nnz) index/value pads; the PS path reuses the framework
ArrayTable (dense/sgd) or the FTRLTable extension table, with
``sync_frequency`` pulls and an AsyncBuffer-style prefetch mirroring
``ps_model.cpp:172-271``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu import log
from multiverso_tpu.dashboard import monitor


@dataclass(frozen=True)
class LogRegConfig:
    input_size: int                 # feature count (bias handled separately)
    output_size: int = 1            # 1 → sigmoid/ftrl; >1 → softmax
    objective: str = "sigmoid"      # "sigmoid" | "softmax" | "ftrl"
    regular: str = "none"           # "none" | "l1" | "l2"
    regular_coef: float = 0.0
    lr: float = 0.1
    minibatch: int = 256
    sparse: bool = False
    max_nnz: int = 64               # padded nnz per sparse sample
    # PS-mode knobs (reference: ps_model.cpp)
    use_ps: bool = False
    sync_frequency: int = 1
    pipeline: bool = False
    # app updater (reference configure.h:91 "[default] [sgd] [ftrl]"):
    # "default" subtracts the RAW gradient (updater.cpp:12-37, Process is a
    # no-op — lr unused); "sgd" scales by a decayed lr:
    # max(1e-3, lr - updates/(lr_coef*minibatch)) (sgd_updater Process);
    # "ftrl" = the optimizer lives in the FTRL table (objective "ftrl").
    updater_type: str = "sgd"
    lr_coef: float = 1e6
    # FTRL hyperparameters
    alpha: float = 0.1
    beta: float = 1.0
    lambda1: float = 1.0
    lambda2: float = 1.0
    seed: int = 0


def _dense_logits(w: jax.Array, x: jax.Array) -> jax.Array:
    """w: (O, I+1) with bias column; x: (B, I)."""
    return x @ w[:, :-1].T + w[:, -1]


def _sparse_logits(w: jax.Array, idx: jax.Array, val: jax.Array) -> jax.Array:
    """idx/val: (B, N) padded with idx=-1 → bias-only contribution masked.
    w gathered per nonzero: (B, N, O)."""
    mask = (idx >= 0).astype(val.dtype)
    rows = jnp.maximum(idx, 0)
    w_feat = w[:, :-1].T[rows]                      # (B, N, O)
    contrib = jnp.einsum("bn,bno->bo", val * mask, w_feat)
    return contrib + w[:, -1]


def _grad_and_loss(config: LogRegConfig):
    """Pure (w, batch) -> (grad, loss) for the configured objective."""
    softmax = config.output_size > 1 and config.objective == "softmax"

    def from_logits(logits, y):
        if softmax:
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
            dlogits = (jnp.exp(logp)
                       - jax.nn.one_hot(y, logits.shape[1])) / y.shape[0]
        else:
            yf = y.astype(logits.dtype).reshape(logits.shape)
            p = jax.nn.sigmoid(logits)
            eps = 1e-7
            loss = -(yf * jnp.log(p + eps)
                     + (1 - yf) * jnp.log(1 - p + eps)).mean()
            dlogits = (p - yf) / y.shape[0]
        return loss, dlogits

    if not config.sparse:
        def gl(w, batch):
            x, y = batch["x"], batch["y"]
            logits = _dense_logits(w, x)
            loss, dlogits = from_logits(logits, y)
            dlogits = dlogits.reshape(x.shape[0], -1)
            grad_w = dlogits.T @ x                  # (O, I)
            grad_b = dlogits.sum(axis=0)            # (O,)
            return jnp.concatenate([grad_w, grad_b[:, None]], axis=1), loss
        return gl

    def gl_sparse(w, batch):
        idx, val, y = batch["idx"], batch["val"], batch["y"]
        logits = _sparse_logits(w, idx, val)
        loss, dlogits = from_logits(logits, y)
        dlogits = dlogits.reshape(idx.shape[0], -1)   # (B, O)
        mask = (idx >= 0).astype(val.dtype)
        rows = jnp.maximum(idx, 0)
        # grad for feature f in sample b: val[b,n] * dlogits[b,:]
        contrib = jnp.einsum("bn,bo->bno", val * mask, dlogits)
        grad_w = jnp.zeros_like(w[:, :-1].T).at[rows.reshape(-1)].add(
            contrib.reshape(-1, dlogits.shape[1]))  # (I, O)
        grad_b = dlogits.sum(axis=0)
        return jnp.concatenate([grad_w.T, grad_b[:, None]], axis=1), loss

    return gl_sparse


def _check_updater_type(config: LogRegConfig) -> None:
    if config.objective not in ("sigmoid", "softmax", "ftrl"):
        log.fatal("objective %r not in sigmoid|softmax|ftrl",
                  config.objective)
    if config.regular not in ("none", "l1", "l2"):
        log.fatal("regular %r not in none|l1|l2", config.regular)
    if config.updater_type not in ("default", "sgd", "ftrl"):
        log.fatal("updater_type %r not in default|sgd|ftrl",
                  config.updater_type)
    if config.updater_type == "ftrl" and config.objective != "ftrl":
        log.fatal("updater_type=ftrl requires objective=ftrl (the FTRL "
                  "optimizer lives in the table)")


def _effective_lr(config: LogRegConfig, updates: int,
                  override: Optional[float]) -> float:
    """Reference SGDUpdater::Process decay; 'default' subtracts raw. The
    1e-3 decay floor never RAISES the rate above the configured lr (a
    config with lr < 1e-3 trains at exactly that lr, undecayed)."""
    if override is not None:
        return override
    if config.updater_type == "default":
        return 1.0
    floor = min(1e-3, config.lr)
    return max(floor, config.lr - updates / (config.lr_coef * config.minibatch))


def _regularizer_grad(config: LogRegConfig):
    if config.regular == "l2":
        return lambda w: config.regular_coef * w
    if config.regular == "l1":
        return lambda w: config.regular_coef * jnp.sign(w)
    return lambda w: jnp.zeros_like(w)


class LogReg:
    """Local-mode model: weights resident on device, jitted SGD train step
    (reference ``Model`` vs ``PSModel`` factory — see :class:`PSLogReg`)."""

    def __init__(self, config: LogRegConfig) -> None:
        if config.objective == "ftrl" and not config.use_ps:
            log.fatal("ftrl objective runs through the FTRL table (use_ps=True)")
        _check_updater_type(config)
        self.config = config
        self._updates = 0
        rng = np.random.default_rng(config.seed)
        self.w = jnp.asarray(
            rng.normal(0, 0.01, (config.output_size, config.input_size + 1))
            .astype(np.float32))
        gl = _grad_and_loss(config)
        reg = _regularizer_grad(config)

        def train_step(w, batch, lr):
            grad, loss = gl(w, batch)
            return w - lr * (grad + reg(w)), loss

        self._train = jax.jit(train_step, donate_argnums=(0,))
        self._predict = jax.jit(self._predict_fn(gl))

    def _predict_fn(self, gl):
        config = self.config

        def predict(w, batch):
            if config.sparse:
                logits = _sparse_logits(w, batch["idx"], batch["val"])
            else:
                logits = _dense_logits(w, batch["x"])
            if config.output_size > 1:
                return jnp.argmax(logits, axis=1)
            return (jax.nn.sigmoid(logits) > 0.5).astype(jnp.int32).reshape(-1)

        return predict

    # -- API ---------------------------------------------------------------
    def update(self, batch: Dict[str, np.ndarray],
               lr: Optional[float] = None) -> float:
        with monitor("LOGREG_UPDATE"):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.w, loss = self._train(
                self.w, batch, _effective_lr(self.config, self._updates, lr))
            self._updates += 1
            return float(loss)

    def load_weights(self, w: np.ndarray) -> None:
        """Warm start (reference: init_model_file, ps_model.cpp:116-154)."""
        self.w = jnp.asarray(np.asarray(w, np.float32).reshape(
            self.config.output_size, self.config.input_size + 1))

    def predict(self, batch: Dict[str, np.ndarray]) -> np.ndarray:
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return np.asarray(self._predict(self.w, batch))

    def test(self, batch: Dict[str, np.ndarray]) -> float:
        pred = self.predict(batch)
        return float((pred == np.asarray(batch["y"]).reshape(-1)).mean())

    def weights(self) -> np.ndarray:
        return np.asarray(self.w)


class PSLogReg(LogReg):
    """Parameter-server mode: weights live in an ArrayTable (dense), a
    SparseTable keyed by feature id (``config.sparse`` — pushes are O(nnz),
    the reference's ``SparseWorkerTable`` contract), or an FTRL table (dense
    accumulator or sparse struct-valued); the local replica syncs every
    ``sync_frequency`` minibatches, optionally via a prefetch double buffer
    (reference: ``ps_model.cpp:172-271`` GetPipelineTable, ``UpdateTable``'s
    sparse branch ``ps_model.cpp:184-200``)."""

    def __init__(self, config: LogRegConfig) -> None:
        import multiverso_tpu as mv
        _check_updater_type(config)
        self.config = config
        self._updates = 0
        self._n = config.output_size * (config.input_size + 1)
        self._bias_key = config.input_size
        gl = _grad_and_loss(config)
        reg = _regularizer_grad(config)
        self._gl = jax.jit(gl)
        self._reg = jax.jit(reg)
        self._predict = jax.jit(self._predict_fn(gl))
        self._keyed = self._keyed_ftrl()
        # table selection (reference: CreateTable in ps_model.cpp — array /
        # sparse / ftrl-sparse keyed on config). Sparse-key tables carry one
        # OUTPUT COLUMN per feature key (width = output_size), so a touched
        # feature ships output_size floats — never the I×O dense gradient.
        if config.sparse:
            from multiverso_tpu.tables.sparse_table import (SparseWorker,
                                                            make_sparse_ftrl)
            mv.register_table_type("sparse", SparseWorker)
            mv.register_table_type("sparse_ftrl", make_sparse_ftrl)
            keys = config.input_size + 1  # + bias key
            if self._keyed:
                # one weight a key, optimizer state alone on the device: the
                # trainer pulls its batch's keys and pushes their gradients,
                # and holds no replica of the key space (`_update_keyed`)
                self.table = mv.create_table(
                    "ftrl", keys, alpha=config.alpha, beta=config.beta,
                    lambda1=config.lambda1, lambda2=config.lambda2)
                self.w = None
                self._pending_adds = []
                self._pending_get = None
                return
            if config.objective == "ftrl":
                self.table = mv.create_table(
                    "sparse_ftrl", keys, width=config.output_size,
                    alpha=config.alpha, beta=config.beta,
                    lambda1=config.lambda1, lambda2=config.lambda2)
            else:
                self.table = mv.create_table(
                    "sparse", keys, width=config.output_size,
                    updater_type="sgd")
        elif config.objective == "ftrl":
            self.table = mv.create_table(
                "ftrl", self._n, alpha=config.alpha, beta=config.beta,
                lambda1=config.lambda1, lambda2=config.lambda2)
        else:
            self.table = mv.create_table(
                "array", self._n, np.float32, updater_type="sgd")
        self.w = jnp.asarray(self._pull())
        self._batches_since_sync = 0
        self._pending_get: Optional[int] = None
        self._pending_adds: list = []

    def _keyed_ftrl(self) -> bool:
        """Sparse FTRL with one output trains through the keyed FTRL table
        (``tables/ftrl_table.py``); wider outputs keep the host
        dictionaries of ``sparse_ftrl``, a row of ``output_size`` a key."""
        config = self.config
        return (config.sparse and config.objective == "ftrl"
                and config.output_size == 1)

    def _compact(self, batch: Dict[str, np.ndarray]):
        """A sparse batch in the space of the keys it names: ``(keys, the
        batch with ``idx`` pointing into them, w)``, ``w`` the ``(1, bucket +
        1)`` weights pulled for them, the bias last, zeros between (a power
        of two, so the jitted step sees few shapes)."""
        idx = np.asarray(batch["idx"])
        touched = np.unique(idx[idx >= 0]).astype(np.int32)
        keys = np.concatenate([touched, [self._bias_key]]).astype(np.int32)
        bucket = 1 << max(3, int(len(touched)).bit_length())
        pulled = self.table.get(keys)
        w = np.zeros((1, bucket + 1), np.float32)
        w[0, :len(touched)] = pulled[:-1]
        w[0, -1] = pulled[-1]
        at = np.searchsorted(touched, np.maximum(idx, 0))
        compact = dict(batch, idx=np.where(idx >= 0, at, -1).astype(idx.dtype))
        return keys, {k: jnp.asarray(v) for k, v in compact.items()}, \
            jnp.asarray(w)

    def _update_keyed(self, batch: Dict[str, np.ndarray]) -> float:
        """Upstream's order (``ps_model.cpp``): pull the batch's keys,
        compute, push their raw gradients; the server runs FTRL."""
        keys, compact, w = self._compact(batch)
        grad, loss = self._gl(w, compact)
        push = np.asarray(grad + self._reg(w))[0]
        self._updates += 1
        self.table.add(keys, np.concatenate(
            [push[:len(keys) - 1], push[-1:]]))
        return float(loss)

    def predict(self, batch: Dict[str, np.ndarray]) -> np.ndarray:
        if not self._keyed:
            return super().predict(batch)
        _, compact, w = self._compact(batch)
        return np.asarray(self._predict(w, compact))

    def weights(self) -> np.ndarray:
        """The dense ``(O, I+1)`` weights; in keyed FTRL mode pulled whole
        from the table (every key: for models small enough to print)."""
        if not self._keyed:
            return super().weights()
        return np.asarray(self.table.get()).reshape(1, -1)

    def _to_w(self, raw) -> np.ndarray:
        """Reconstruct the dense (O, I+1) replica from a table reply."""
        o, cols = self.config.output_size, self.config.input_size + 1
        if self.config.sparse:
            keys, vals = raw
            w = np.zeros((o, cols), np.float32)
            if len(keys):
                w[:, keys] = vals.T
            return w
        return np.asarray(raw).reshape(o, cols)

    def _pull(self) -> np.ndarray:
        return self._to_w(self.table.get())

    def update(self, batch: Dict[str, np.ndarray],
               lr: Optional[float] = None) -> float:
        if self._keyed:
            return self._update_keyed(batch)
        lr = _effective_lr(self.config, self._updates, lr)
        self._updates += 1
        idx_np = np.asarray(batch["idx"]) if self.config.sparse else None
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        grad, loss = self._gl(self.w, batch)
        push = grad + self._reg(self.w)
        if self.config.sparse:
            # O(nnz) push: only the minibatch's touched feature columns (+
            # bias) cross the boundary (reference sparse_table.h AddAsync).
            # Regularization is LAZY in sparse mode: a feature's L1/L2 decay
            # is applied only when a batch touches it — the standard sparse-
            # PS trade (decaying all I columns would make the push O(I·O))
            touched = np.unique(idx_np[idx_np >= 0]).astype(np.int64)
            keys = np.concatenate([touched, [self._bias_key]])
            cols = np.asarray(push)[:, keys].T          # (nnz, O)
            if self.config.objective == "ftrl":
                mid = self.table.add_async(keys, cols)  # server runs FTRL
            else:
                mid = self.table.add_async(keys, lr * cols)  # sgd updater: -=
        elif self.config.objective == "ftrl":
            mid = self.table.add_async(np.asarray(push).reshape(-1))
        else:
            # sgd updater applies data -= delta: ship lr-scaled gradient
            mid = self.table.add_async(lr * np.asarray(push).reshape(-1))
        self._pending_adds.append(mid)
        self._batches_since_sync += 1
        if self._batches_since_sync >= self.config.sync_frequency:
            self._sync()
        return float(loss)

    def _sync(self) -> None:
        self._batches_since_sync = 0
        # drain outstanding add handles (the dispatcher has applied them
        # before any later get — FIFO — but their completions must be
        # reclaimed or the pending map grows for the whole run)
        for mid in self._pending_adds:
            self.table.wait(mid)
        self._pending_adds.clear()
        with monitor("PS_LOGREG_PULL"):
            if self.config.pipeline and self._pending_get is not None:
                raw = self.table.wait(self._pending_get)
                self.w = jnp.asarray(self._to_w(raw))
                self._pending_get = self.table.get_async()
            elif self.config.pipeline:
                self._pending_get = self.table.get_async()
                self.w = jnp.asarray(self._pull())
            else:
                self.w = jnp.asarray(self._pull())

    def finish(self) -> None:
        for mid in self._pending_adds:
            self.table.wait(mid)
        self._pending_adds.clear()
        if self._pending_get is not None:
            self.table.wait(self._pending_get)
            self._pending_get = None
        if not self._keyed:
            self.w = jnp.asarray(self._pull())

    def load_weights(self, w: np.ndarray) -> None:
        """Warm start THROUGH the table so every worker sees it (reference
        PSModel::Load pushed the loaded model as a delta the same way,
        ps_model.cpp:116-154). Not available for FTRL tables: their z/n
        state cannot be reconstructed from dense weights."""
        if self.config.objective == "ftrl":
            log.fatal("init model into an FTRL table is unsupported "
                      "(optimizer state is not derivable from weights)")
        o, cols = self.config.output_size, self.config.input_size + 1
        w = np.asarray(w, np.float32).reshape(o, cols)
        current = self._pull()
        delta = current - w  # sgd-family server tables apply data -= delta
        if self.config.sparse:
            keys = np.arange(cols, dtype=np.int64)
            self.table.add(keys, delta.T)
        else:
            self.table.add(delta.reshape(-1))
        self.w = jnp.asarray(self._pull())


def make_model(config: LogRegConfig) -> LogReg:
    """Reference factory (`Model::Get` on use_ps): local vs PS model."""
    return PSLogReg(config) if config.use_ps else LogReg(config)


# -- data ------------------------------------------------------------------

def parse_libsvm_line(line: str, max_nnz: int) -> Tuple[int, np.ndarray, np.ndarray]:
    parts = line.split()
    label = int(float(parts[0]))
    idx = np.full(max_nnz, -1, np.int32)
    val = np.zeros(max_nnz, np.float32)
    for i, tok in enumerate(parts[1:max_nnz + 1]):
        k, _, v = tok.partition(":")
        idx[i] = int(k)
        val[i] = float(v) if v else 1.0
    return label, idx, val


def load_libsvm_native(path: str, max_nnz: int = 64
                       ) -> Optional[Dict[str, np.ndarray]]:
    """Native multithreaded libsvm parse (``native/text_reader.cpp`` — the
    analog of the reference's C++ sample readers, reader.cpp). Returns
    None when the .so isn't built or the parse fails; output is
    byte-identical to the Python path (asserted by tests/test_lr_io.py)."""
    import ctypes
    import os

    from multiverso_tpu.utils.quantization import _load_native
    lib = _load_native()
    if lib is None or not os.path.isfile(path):
        return None

    class _Result(ctypes.Structure):
        _fields_ = [("n_rows", ctypes.c_longlong),
                    ("max_nnz", ctypes.c_int),
                    ("labels", ctypes.POINTER(ctypes.c_int)),
                    ("indices", ctypes.POINTER(ctypes.c_int)),
                    ("values", ctypes.POINTER(ctypes.c_float))]

    try:
        fn = lib.MVTR_ParseLibsvmFile
    except AttributeError:
        return None
    fn.restype = ctypes.c_int
    fn.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(_Result)]
    lib.MVTR_FreeResult.argtypes = [ctypes.POINTER(_Result)]
    res = _Result()
    # os.fsencode: filenames with surrogate escapes (non-UTF-8 on-disk
    # names) must round-trip, not raise UnicodeEncodeError
    if fn(os.fsencode(path), int(max_nnz), ctypes.byref(res)) != 0:
        return None
    try:
        n = int(res.n_rows)
        y = np.ctypeslib.as_array(res.labels, (n,)).astype(np.int32) \
            if n else np.zeros(0, np.int32)
        idx = (np.ctypeslib.as_array(res.indices, (n, max_nnz))
               .astype(np.int32) if n
               else np.full((0, max_nnz), -1, np.int32))
        val = (np.ctypeslib.as_array(res.values, (n, max_nnz))
               .astype(np.float32) if n
               else np.zeros((0, max_nnz), np.float32))
        return {"y": y, "idx": idx, "val": val}
    finally:
        lib.MVTR_FreeResult(ctypes.byref(res))


def load_libsvm(path: str, max_nnz: int = 64) -> Dict[str, np.ndarray]:
    """Load a LibSVM-format file into padded sparse batch arrays. Plain
    local files take the native multithreaded parser when the .so is
    built; stream URIs (mvfs://, gs://, mem://) use the Python path."""
    if "://" not in path:
        native = load_libsvm_native(path, max_nnz)
        if native is not None:
            return native
    from multiverso_tpu.io import TextReader
    labels, idxs, vals = [], [], []
    reader = TextReader(path)
    while (line := reader.get_line()) is not None:
        if not line.strip():
            continue
        y, idx, val = parse_libsvm_line(line, max_nnz)
        labels.append(y)
        idxs.append(idx)
        vals.append(val)
    reader.close()
    if not labels:  # empty/all-blank file: same contract as the native path
        return {"y": np.zeros(0, np.int32),
                "idx": np.full((0, max_nnz), -1, np.int32),
                "val": np.zeros((0, max_nnz), np.float32)}
    return {"y": np.array(labels, np.int32), "idx": np.stack(idxs),
            "val": np.stack(vals)}


def minibatches(data: Dict[str, np.ndarray], batch_size: int,
                rng: Optional[np.random.Generator] = None
                ) -> Iterator[Dict[str, np.ndarray]]:
    n = len(data["y"])
    order = rng.permutation(n) if rng is not None else np.arange(n)
    for i in range(0, n - batch_size + 1, batch_size):
        sl = order[i:i + batch_size]
        yield {k: v[sl] for k, v in data.items()}
