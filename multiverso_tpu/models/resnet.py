"""CIFAR ResNet family + ASGD training — the deep-learning workload behind
the reference's only published benchmark numbers.

The reference itself ships no model code for this: its numbers come from
training torch/lasagne ResNet-32 on CIFAR-10 through the binding layer
(``binding/lua/docs/BENCHMARK.md:37-39``, ``binding/python/docs/
BENCHMARK.md:57-59``) — N processes, each on its own GPU, asynchronously
syncing parameters through Multiverso tables (ASGD). This module provides
the TPU-native counterpart so the framework's ext layer has a real deep
net to carry:

- the same model family (He et al.'s CIFAR ResNet-n, n = 6k+2: 3 stages of
  k BasicBlocks at 16/32/64 channels, option-A parameter-free shortcuts —
  the 464,154-param ResNet-32 in ``binding/python/docs/BENCHMARK.md:57``
  is exactly this with k=5);
- a jitted SGD+momentum+weight-decay train step (batch 128, lr 0.1 — the
  published config), bfloat16 matmuls on the MXU with f32 accumulation;
- :class:`ASGDTrainer`: worker threads with local replicas syncing deltas
  through ONE PS ArrayTable via ``PytreeParamManager`` every ``sync_freq``
  batches — the binding examples' add/get cadence
  (``binding/python/multiverso/theano_ext/lasagne_ext/param_manager.py``).

TPU-first notes: on one chip, data parallelism belongs to XLA (batch
sharding under jit) — worker threads exist to exercise the PS/ASGD product
contract, and to scale past one host the same trainer runs against
``mv.serve()``/``mv.remote_connect()`` workers. Norm layers default to
GroupNorm (batch-size independent, no mutable state crossing the sync
boundary); BatchNorm is available for strict parity, with running stats
kept worker-local like the reference's per-process torch models.
"""

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import log

try:
    import flax.linen as nn
except Exception as e:  # pragma: no cover - flax is baked into the image
    nn = None
    _flax_err = e


@dataclass
class ResNetConfig:
    depth: int = 32          # 6k+2: 20, 32, 44, 56...
    num_classes: int = 10
    width: int = 16          # channels of stage 1 (paper/benchmark: 16)
    norm: str = "group"      # "group" (TPU default) | "batch" (parity)
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16   # MXU-native; f32 accumulation

    @property
    def blocks_per_stage(self) -> int:
        if (self.depth - 2) % 6 != 0:
            log.fatal("ResNet depth must be 6k+2, got %d", self.depth)
        return (self.depth - 2) // 6


def _norm(config: ResNetConfig, train: bool):
    if config.norm == "batch":
        return lambda: nn.BatchNorm(use_running_average=not train,
                                    momentum=0.9, dtype=config.compute_dtype,
                                    param_dtype=config.param_dtype)
    return lambda: nn.GroupNorm(num_groups=8, dtype=config.compute_dtype,
                                param_dtype=config.param_dtype)


class BasicBlock(nn.Module):
    """3x3+3x3 residual block with option-A shortcut (stride-2 subsample +
    zero channel padding — parameter-free, the CIFAR-paper/benchmark
    variant, unlike the 1x1-conv option B of ImageNet ResNets)."""
    config: ResNetConfig
    channels: int
    stride: int = 1

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = self.config
        norm = _norm(c, train)
        y = nn.Conv(self.channels, (3, 3), (self.stride, self.stride),
                    padding=1, use_bias=False, dtype=c.compute_dtype,
                    param_dtype=c.param_dtype)(x)
        y = norm()(y)
        y = nn.relu(y)
        y = nn.Conv(self.channels, (3, 3), padding=1, use_bias=False,
                    dtype=c.compute_dtype, param_dtype=c.param_dtype)(y)
        y = norm()(y)
        if x.shape[-1] != self.channels or self.stride != 1:
            x = x[:, ::self.stride, ::self.stride, :]
            pad = self.channels - x.shape[-1]
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad)))
        return nn.relu(y + x)


class CifarResNet(nn.Module):
    config: ResNetConfig

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = self.config
        x = x.astype(c.compute_dtype)
        x = nn.Conv(c.width, (3, 3), padding=1, use_bias=False,
                    dtype=c.compute_dtype, param_dtype=c.param_dtype)(x)
        x = _norm(c, train)()(x)
        x = nn.relu(x)
        for stage, mult in enumerate((1, 2, 4)):
            for block in range(c.blocks_per_stage):
                stride = 2 if (stage > 0 and block == 0) else 1
                x = BasicBlock(c, c.width * mult, stride)(x, train)
        x = x.mean(axis=(1, 2))                       # global average pool
        x = nn.Dense(c.num_classes, dtype=jnp.float32,
                     param_dtype=c.param_dtype)(x)    # f32 logits
        return x


def init_resnet(config: ResNetConfig, rng: jax.Array,
                input_shape: Tuple[int, ...] = (1, 32, 32, 3)):
    """Returns (model, variables). ``variables`` holds ``params`` and, for
    norm="batch", ``batch_stats``."""
    if nn is None:  # pragma: no cover
        log.fatal("flax unavailable: %s", _flax_err)
    model = CifarResNet(config)
    variables = model.init(rng, jnp.zeros(input_shape, jnp.float32))
    return model, variables


def make_train_step(model, config: ResNetConfig) -> Callable:
    """jitted step(variables, batch) -> (variables, loss). SGD + momentum +
    decoupled weight decay, the published benchmark config
    (``binding/python/docs/BENCHMARK.md:57``: batch 128, lr 0.1). Momentum
    state rides inside ``variables['opt_momentum']`` so the whole training
    state is one pytree (checkpoint- and donation-friendly)."""
    has_bn = config.norm == "batch"

    def loss_fn(params, state, images, labels):
        vars_in = {"params": params, **state}
        if has_bn:
            logits, updates = model.apply(vars_in, images, train=True,
                                          mutable=["batch_stats"])
        else:
            logits, updates = model.apply(vars_in, images, train=True), {}
        one_hot = jax.nn.one_hot(labels, logits.shape[-1])
        loss = -(one_hot * jax.nn.log_softmax(logits)).sum(-1).mean()
        return loss, updates

    def step(variables, images, labels, lr):
        params = variables["params"]
        mom = variables["opt_momentum"]
        state = ({"batch_stats": variables["batch_stats"]} if has_bn else {})
        (loss, updates), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, state, images, labels)
        new_mom = jax.tree.map(
            lambda m, g, p: config.momentum * m + g + config.weight_decay * p,
            mom, grads, params)
        new_params = jax.tree.map(lambda p, m: p - lr * m, params, new_mom)
        out = {"params": new_params, "opt_momentum": new_mom}
        if has_bn:
            out["batch_stats"] = updates["batch_stats"]
        return out, loss

    return jax.jit(step, donate_argnums=(0,))


def train_state(model, config: ResNetConfig, variables) -> dict:
    """Wrap init variables into the train-step pytree (zero momentum)."""
    out = {"params": variables["params"],
           "opt_momentum": jax.tree.map(jnp.zeros_like, variables["params"])}
    if config.norm == "batch":
        out["batch_stats"] = variables["batch_stats"]
    return out


def evaluate(model, config: ResNetConfig, variables, images, labels,
             batch: int = 256) -> float:
    """Top-1 accuracy; BN uses running stats (use_running_average)."""
    has_bn = config.norm == "batch"
    vars_in = {"params": variables["params"]}
    if has_bn:
        vars_in["batch_stats"] = variables["batch_stats"]

    @jax.jit
    def logits_fn(v, x):
        return model.apply(v, x, train=False, mutable=False)

    correct = 0
    for i in range(0, len(images), batch):
        x = jnp.asarray(images[i:i + batch])
        lg = np.asarray(logits_fn(vars_in, x))
        correct += int((lg.argmax(-1) == labels[i:i + batch]).sum())
    return correct / len(images)


class ASGDTrainer:
    """N worker threads, each with a local replica, syncing through ONE
    ArrayTable via PytreeParamManager — the reference benchmark's topology
    (``binding/lua/docs/BENCHMARK.md:39``: 8 procs, sync per batch) with
    threads instead of MPI ranks; the same code drives remote workers via
    mv.remote_connect (tables are process-transparent).

    Only ``params`` crosses the wire: momentum is worker-local (the
    reference's torch optimizers were per-process too) and BN running
    stats, if any, stay local (per-process there as well)."""

    def __init__(self, config: ResNetConfig, workers: int = 4,
                 sync_freq: int = 1, input_shape=(32, 32, 3),
                 pipeline: bool = False) -> None:
        import multiverso_tpu as mv
        self.mv = mv
        self.config = config
        self.workers = workers
        self.sync_freq = sync_freq
        # pipeline=True: per-batch syncs use the one-round-stale
        # sync_pipelined path (the reference LR pipeline's double-buffer
        # shape) — the sync submission overlaps the next batch's compute
        self.pipeline = bool(pipeline)
        rng = jax.random.PRNGKey(0)
        self.model, variables = init_resnet(
            config, rng, (1,) + tuple(input_shape))
        self.step_fn = make_train_step(self.model, config)
        self._state0 = train_state(self.model, config, variables)
        self.final_state = None
        # ONE manager (one table) for the trainer's lifetime, created here
        # so CheckpointDriver([trainer.manager.table], ...) can be set up
        # BEFORE train() runs (periodic mid-training snapshots)
        from multiverso_tpu.ext import PytreeParamManager
        self.manager = PytreeParamManager(self._state0["params"])

    def train(self, images: np.ndarray, labels: np.ndarray, epochs: int = 1,
              batch: int = 128, lr: Optional[float] = None) -> dict:
        """Shard the data across workers, run ASGD, return the final state
        with the merged global params from the table."""
        import threading

        mv, cfg = self.mv, self.config
        lr = cfg.lr if lr is None else lr
        shard = len(images) // self.workers
        # each worker thread gets its own view of the shared manager table,
        # with a private delta baseline
        manager = self.manager
        results = [None] * self.workers

        def work(slot: int):
            with mv.worker(slot):
                # device=True: sync never leaves HBM for in-process workers
                # (remote clients fall back to the host path automatically)
                view = manager.worker_view(device=True)
                # fresh per-worker buffers: the step donates its state, so
                # sharing _state0's arrays would let worker A's first step
                # invalidate everyone else's inputs
                state = jax.tree.map(jnp.copy, self._state0)
                state["params"] = view.params   # current global init
                n_batches = 0
                lo = slot * shard
                xs, ys = images[lo:lo + shard], labels[lo:lo + shard]
                order = np.arange(len(xs))
                rng = np.random.default_rng(slot)
                for _ in range(epochs):
                    rng.shuffle(order)
                    for i in range(0, len(xs) - batch + 1, batch):
                        idx = order[i:i + batch]
                        state, _ = self.step_fn(state, jnp.asarray(xs[idx]),
                                                jnp.asarray(ys[idx]), lr)
                        n_batches += 1
                        if n_batches % self.sync_freq == 0:
                            state["params"] = (
                                view.sync_pipelined(state["params"])
                                if self.pipeline
                                else view.sync(state["params"]))
                state["params"] = view.sync(state["params"])
                results[slot] = state

        threads = [threading.Thread(target=work, args=(s,), daemon=True)
                   for s in range(self.workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for slot, r in enumerate(results):
            if r is None:
                log.fatal("ASGD worker %d died before finishing", slot)
        self.final_state = dict(results[0])
        # worker 0's last pull may predate peers' final pushes; re-read the
        # settled global value
        self.final_state["params"] = manager.worker_view().params
        return self.final_state


def synthetic_cifar(n: int, num_classes: int = 10, seed: int = 0,
                    shape=(32, 32, 3)) -> Tuple[np.ndarray, np.ndarray]:
    """Learnable CIFAR-shaped task: each class is a fixed random spatial
    template plus noise — linearly separable in principle but requiring a
    real forward pass to fit. Used by tests and examples."""
    rng = np.random.default_rng(seed)
    templates = rng.normal(size=(num_classes,) + shape).astype(np.float32)
    labels = rng.integers(0, num_classes, n)
    images = (0.6 * templates[labels]
              + rng.normal(size=(n,) + shape).astype(np.float32))
    return images.astype(np.float32), labels.astype(np.int32)
