"""Word2Vec (skip-gram / CBOW, negative-sampling / hierarchical-softmax) —
the flagship application, rebuilt TPU-first.

Reference capability (not copied): the WordEmbedding app — skip-gram/CBOW
with HS or negative sampling trained against parameter-server matrix tables,
with a block loader thread and words/sec logging
(``Applications/WordEmbedding/src/{wordembedding,trainer,distributed_wordembedding}.cpp``).

TPU-native re-design (how it differs from the reference's scalar hot loops):

* The entire training step is ONE jitted function: embedding gathers, the
  (B, 1+K, D) score einsum (MXU), sigmoid gradients, and scatter-add row
  updates all fuse on device. The reference's per-sample dot-product loops
  (``wordembedding.cpp:57-150``) become batched contractions.
* Negative sampling happens *inside* the jit via inverse-CDF
  ``searchsorted`` on the unigram^0.75 distribution — no 1e8-slot host table.
* Hierarchical softmax is a masked fixed-length einsum over Huffman
  codes/points prepared by :class:`~multiverso_tpu.models.vocab.HuffmanEncoder`.
* Two trainers: :class:`DeviceTrainer` keeps embeddings resident in HBM
  sharded over the mesh (the TPU-era fast path); :class:`PSTrainer` drives
  the MatrixTable Get/Add API with delta = trained − cached exactly like the
  reference's ``RequestParameter``/``AddDeltaParameter`` client.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, Iterable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu import log
from multiverso_tpu.models.vocab import Dictionary, HuffmanEncoder
from multiverso_tpu.ops.sampling import unigram_negative_sampler
from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.utils import async_upload, next_pow2 as _next_pow2


@dataclass(frozen=True)
class Word2VecConfig:
    vocab_size: int
    dim: int = 128
    window: int = 5
    negatives: int = 5
    mode: str = "sg"          # "sg" | "cbow"
    objective: str = "ns"     # "ns" | "hs"
    lr: float = 0.025
    batch_pairs: int = 8192   # pairs per device step (pair-mode trainers)
    block_tokens: int = 8192  # tokens per device step (block-mode trainer)
    sample: float = 1e-3      # subsampling threshold
    max_code_length: int = 40
    grad_combine: str = "sum"  # "sum" (bounded per-occurrence SGD) | "mean"
    # Stability bound for "sum": a row whose occurrences would move it more
    # than max_row_step (in units of its mean per-occurrence gradient) gets
    # its batch update clamped to that budget. Rows with lr·dups <= the bound
    # see exact per-occurrence SGD — the realistic regime (lr 0.025, subsampled
    # corpora); hot rows on unsubsampled zipf corpora no longer blow up from
    # dup_count×lr steps applied at the same stale weights.
    max_row_step: float = 1.0
    # Block-mode negative sharing: one K-sample set serves a group of
    # neg_sharing consecutive centers (1 = per-center, the word2vec.c-like
    # default). Negatives are noise — sharing across a few adjacent
    # centers preserves quality (convergence-tested at 8) while cutting
    # negative row gather/scatter traffic by the factor and turning the
    # negative score into a bigger, MXU-friendlier contraction.
    neg_sharing: int = 1
    seed: int = 1

    def __post_init__(self):
        if self.grad_combine not in ("sum", "mean"):
            raise ValueError(
                f"grad_combine must be 'sum' or 'mean', got {self.grad_combine!r}")
        if self.neg_sharing < 1:
            raise ValueError(
                f"neg_sharing must be >= 1, got {self.neg_sharing}")
        if self.block_tokens % self.neg_sharing:
            raise ValueError(
                f"neg_sharing {self.neg_sharing} must divide block_tokens "
                f"{self.block_tokens}")


# -- params -----------------------------------------------------------------

def init_params(config: Word2VecConfig, mesh=None,
                pad_rows_to: int = 1) -> Dict[str, jax.Array]:
    """w_in ~ U(-0.5/dim, 0.5/dim); w_out zeros (word2vec convention).
    When a mesh is given, rows shard over its 'model' (or first) axis."""
    v = config.vocab_size
    out_rows = v if config.objective == "ns" else max(v - 1, 1)
    rng = np.random.default_rng(config.seed)

    def make(rows: int, random_init: bool) -> np.ndarray:
        true_rows = rows
        rows += 1  # scratch sentinel row: masked pairs scatter here
        if mesh is not None:
            shards = mesh.devices.size if "model" not in mesh.shape else mesh.shape["model"]
            rows = mesh_lib.pad_to_multiple(rows, max(shards, pad_rows_to))
        arr = np.zeros((rows, config.dim), dtype=np.float32)
        if random_init:
            arr[:true_rows] = rng.uniform(-0.5 / config.dim, 0.5 / config.dim,
                                          size=(true_rows, config.dim))
        return arr

    w_in = make(v, random_init=True)
    w_out = make(out_rows, random_init=False)
    if mesh is not None:
        axis = "model" if "model" in mesh.shape else list(mesh.shape)[0]
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(axis, None))
        return {"w_in": jax.device_put(w_in, sharding),
                "w_out": jax.device_put(w_out, sharding)}
    return {"w_in": jnp.asarray(w_in), "w_out": jnp.asarray(w_out)}


# -- the jitted step --------------------------------------------------------

def _ns_targets(key: jax.Array, contexts: jax.Array, sampler,
                negatives: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(ids, labels, mask) for negative sampling: 1 positive + K alias-sampled
    (searchsorted binary search is ~50x slower on TPU — see ops/sampling)."""
    b = contexts.shape[0]
    negs = sampler(key, (b, negatives))
    ids = jnp.concatenate([contexts[:, None], negs], axis=1)        # (B, 1+K)
    labels = jnp.zeros_like(ids, dtype=jnp.float32).at[:, 0].set(1.0)
    mask = jnp.ones_like(labels)
    return ids, labels, mask


def _hs_targets(targets: jax.Array, codes: jax.Array, points: jax.Array,
                code_mask: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(ids, labels, mask) for hierarchical softmax over Huffman paths."""
    ids = points[targets]                                           # (B, L)
    labels = 1.0 - codes[targets].astype(jnp.float32)               # (B, L)
    mask = code_mask[targets]                                       # (B, L)
    return ids, labels, mask


def _scale_from_count(count, lr, cap):
    """Stability clamp from a per-row occurrence count: rows whose
    occurrence-weighted step budget lr·count exceeds ``cap`` are scaled so
    their total batch step equals the cap; all others keep exact sum
    semantics."""
    return jnp.minimum(1.0, cap / jnp.maximum(lr * count, 1e-6))


def _row_step_scale(num_rows: int, row_ids, occ_weights, lr, cap):
    """:func:`_scale_from_count` over a scatter-aggregated count.
    row_ids/occ_weights may be any matching shape."""
    count = jnp.zeros(num_rows, jnp.float32).at[row_ids.reshape(-1)].add(
        occ_weights.reshape(-1).astype(jnp.float32))
    return _scale_from_count(count, lr, cap)


def _sgns_core(w_in, w_out, in_ids, in_weights, out_ids, labels, mask, lr,
               combine: str = "sum", max_row_step: float = 1.0):
    """Shared gradient core: input rows vs output rows, masked logistic loss.

    in_ids: (B, C) input rows averaged with in_weights (C=1 for skip-gram);
    out_ids/labels/mask: (B, T) output rows and their logistic targets.
    Returns updated (w_in, w_out, loss). All contractions are MXU einsums;
    row updates are scatter-adds (duplicates accumulate correctly).
    """
    v_rows = w_in[in_ids]                                           # (B, C, D)
    v = jnp.einsum("bc,bcd->bd", in_weights, v_rows)                # (B, D)
    u = w_out[out_ids]                                              # (B, T, D)
    scores = jnp.einsum("bd,btd->bt", v, u)                         # (B, T)
    p = jax.nn.sigmoid(scores)
    g = (p - labels) * mask                                         # (B, T)
    loss = -jnp.sum(mask * jax.nn.log_sigmoid(
        jnp.where(labels > 0.5, scores, -scores))) / jnp.maximum(mask.sum(), 1.0)
    grad_v = jnp.einsum("bt,btd->bd", g, u)                         # (B, D)
    grad_u = jnp.einsum("bt,bd->btd", g, v)                         # (B, T, D)
    grad_rows = jnp.einsum("bc,bd->bcd", in_weights, grad_v)        # (B, C, D)
    dim = w_in.shape[1]
    # combine="sum" (default): per-occurrence SGD — each sample contributes
    # its own lr-step, like the reference's sequential hot loop — with a
    # stability bound: the batched scatter applies all of a row's duplicate
    # steps at the SAME stale weights (no sequential sigmoid feedback), so a
    # hot row's total step is clamped to max_row_step gradient-units.
    # Rows with lr·dups <= the bound are untouched (exact sum semantics).
    # combine="mean": one averaged lr-step per row per batch — bounded for
    # any corpus, but the weakened per-occurrence negative pressure lets
    # embeddings collapse on long runs (measured: parity-cluster separation
    # +0.34 at 10 epochs decays to +0.01 by 20 epochs).
    flat_in = in_ids.reshape(-1)
    flat_out = out_ids.reshape(-1)
    gin = grad_rows.reshape(-1, dim)
    gout = grad_u.reshape(-1, dim)
    if combine == "mean":
        # count-divide + XLA scatter-add, deliberately NOT a fused
        # sort→segment-mean→unique-row scatter: that variant was built and
        # measured (r2) at 10.8 vs 6.3 ms/block on v5e for this workload —
        # the in-jit argsort over ~123k ids costs more than duplicate
        # pre-combining saves; it would only pay off under extreme
        # duplication or when a stateful updater needs unique rows.
        in_count = jnp.zeros(w_in.shape[0], v.dtype).at[flat_in].add(1.0)
        out_count = jnp.zeros(w_out.shape[0], v.dtype).at[flat_out].add(1.0)
        gin = gin / in_count[flat_in][:, None]
        gout = gout / out_count[flat_out][:, None]
    else:
        # occurrence-units: live in-entries (weight>0), mask-weighted out-entries
        in_scale = _row_step_scale(w_in.shape[0], in_ids,
                                   (in_weights > 0), lr, max_row_step)
        out_scale = _row_step_scale(w_out.shape[0], out_ids, mask, lr,
                                    max_row_step)
        gin = gin * in_scale[flat_in][:, None]
        gout = gout * out_scale[flat_out][:, None]
    w_in = w_in.at[flat_in].add(-lr * gin)
    w_out = w_out.at[flat_out].add(-lr * gout)
    return w_in, w_out, loss


def make_train_step(config: Word2VecConfig, dictionary: Dictionary,
                    huffman: Optional[HuffmanEncoder] = None):
    """Build the jitted step(params, key, batch, lr) -> (params, loss).

    batch: for sg — dict(centers (B,), contexts (B,));
           for cbow — dict(centers (B,), context_block (B, 2W) id or -1).
    """
    if config.objective == "ns":
        sampler = unigram_negative_sampler(dictionary.counts)
        hs_arrays = None
    else:
        if huffman is None:
            huffman = HuffmanEncoder(dictionary.counts, config.max_code_length)
        hs_arrays = (jnp.asarray(huffman.codes), jnp.asarray(huffman.points),
                     jnp.asarray(huffman.mask()))
        sampler = None

    def step(params, key, batch, lr):
        centers = batch["centers"]
        if config.mode == "sg":
            in_ids = centers[:, None]
            in_weights = jnp.ones_like(in_ids, dtype=jnp.float32)
            predict = batch["contexts"]
        else:  # cbow: average valid context embeddings, predict the center
            ctx = batch["context_block"]                            # (B, 2W)
            valid = (ctx >= 0).astype(jnp.float32)
            in_ids = jnp.maximum(ctx, 0)
            in_weights = valid / jnp.maximum(valid.sum(1, keepdims=True), 1.0)
            predict = centers
        if config.objective == "ns":
            out_ids, labels, mask = _ns_targets(key, predict, sampler,
                                                config.negatives)
        else:
            codes, points, code_mask = hs_arrays
            out_ids, labels, mask = _hs_targets(predict, codes, points, code_mask)
        pair_mask = batch.get("pair_mask")
        if pair_mask is not None:  # tail-padded batch: dead pairs contribute
            in_weights = in_weights * pair_mask[:, None]  # nothing on either
            mask = mask * pair_mask[:, None]              # side of the dot
        w_in, w_out, loss = _sgns_core(params["w_in"], params["w_out"],
                                       in_ids, in_weights, out_ids, labels,
                                       mask, lr, config.grad_combine,
                                       config.max_row_step)
        return {"w_in": w_in, "w_out": w_out}, loss

    return jax.jit(step, donate_argnums=(0,))


def make_block_train_step(config: Word2VecConfig, dictionary: Dictionary,
                          jit: bool = True, neg_table: bool = False):
    """Block-mode step: the host ships ONE int32 token block per step (pad
    with -1); window pair extraction, dynamic-window masking, negative
    sampling, and the update all happen in-jit. This minimizes host↔device
    traffic (the TPU-era analog of the reference's block pipeline, which
    existed to hide *network* latency; here it removes PCIe/host latency).

    step(params, key, block (T,), lr) -> (params, loss). Skip-gram + NS.
    Pass ``jit=False`` to get the raw traceable function (for scan wrappers).
    """
    if config.mode != "sg" or config.objective != "ns":
        log.fatal("block step supports sg+ns (the benchmark path)")
    sampler = None if neg_table else unigram_negative_sampler(dictionary.counts)
    window = config.window
    negatives = config.negatives
    combine = config.grad_combine
    offsets = np.array([o for o in range(-window, window + 1) if o != 0],
                       dtype=np.int32)                               # (2W,)

    def step(params, key, block, lr, neg_slots=None, with_pairs=False):
        # Structured form: keep the (T, 2W) pair layout instead of a flat
        # pair list. The input row of a center is gathered ONCE for its 2W
        # pairs, negatives are shared per center, and gradients are
        # pre-reduced over the window axis before scattering — ~10× less
        # HBM gather/scatter traffic than the flat-pair formulation.
        w_in, w_out = params["w_in"], params["w_out"]
        sentinel_in = w_in.shape[0] - 1
        sentinel_out = w_out.shape[0] - 1
        t = block.shape[0]
        k_win, k_neg = jax.random.split(key)
        valid_tok = block >= 0
        # dynamic window size per center position
        b = jax.random.randint(k_win, (t,), 1, window + 1)           # (T,)
        pos = jnp.arange(t)
        ctx_pos = pos[:, None] + offsets[None, :]                    # (T, 2W)
        in_range = (ctx_pos >= 0) & (ctx_pos < t)
        ctx_pos = jnp.clip(ctx_pos, 0, t - 1)
        contexts = block[ctx_pos]                                    # (T, 2W)
        pair_mask = (in_range
                     & (jnp.abs(offsets)[None, :] <= b[:, None])
                     & valid_tok[:, None] & (contexts >= 0))         # (T, 2W)
        pm = pair_mask.astype(jnp.float32)
        npairs = pm.sum(axis=1)                                      # (T,)
        active = (npairs > 0)

        centers_id = jnp.where(valid_tok & active, block, sentinel_in)
        blk_out_ids = jnp.where(valid_tok, block, sentinel_out)      # (T,)
        # grouped negatives: one K-set serves G consecutive centers (G=1 =
        # per-center); cuts negative row traffic G-fold and turns the
        # negative contraction into an MXU-shaped (G, D)x(K, D) block
        G = config.neg_sharing  # validated >= 1, divides block_tokens
        if t % G:  # defensive: caller passed a non-config-sized block
            log.fatal("neg_sharing %d must divide block length %d", G, t)
        tg = t // G
        act_g = active.reshape(tg, G)
        if neg_table:
            # compact-space mode (PS fast path): negatives come from a
            # host-built slot-alias table whose duplicates encode the
            # unigram^0.75 marginal exactly — uniform draws over it
            # reproduce the sampler's distribution inside the pulled pool
            draws = jax.random.randint(k_neg, (tg, negatives), 0,
                                       neg_slots.shape[0])
            negs_c = neg_slots[draws]                                # (TG, K)
        else:
            negs_c = sampler(k_neg, (tg, negatives))                 # (TG, K)
        negs_id = jnp.where(act_g.any(axis=1)[:, None], negs_c,
                            sentinel_out)                            # (TG, K)

        v = w_in[centers_id]                                         # (T, D)
        # Block-local context reuse: every positive context row IS some
        # block position's own w_out row, so ONE (T, D) gather serves all
        # 2W offsets via vector rolls -- replacing the (T, 2W, D) HBM
        # gather AND the 2W*T-row scatter with VPU shifts. Row-granular
        # HBM ops run at a ~13ns/row descriptor floor (ops/pallas_rows.py),
        # so shrinking the out side from (2W+K)*T rows to (1+K)*T rows is
        # the dominant win (measured: 0.88 -> ~1.3 M words/s).
        u_blk = w_out[blk_out_ids]                                   # (T, D)
        u_neg = w_out[negs_id]                                       # (TG, K, D)
        vg = v.reshape(tg, G, v.shape[1])                            # (TG, G, D)

        s_neg = jnp.einsum("gcd,gkd->gck", vg, u_neg)                # (TG, G, K)
        # negatives are shared across the center's pairs -> their per-pair
        # gradients coincide; the pair-mean is just sigmoid(s)
        g_neg = jax.nn.sigmoid(s_neg) * act_g[:, :, None]            # (TG, G, K)

        loss_pos = jnp.float32(0.0)
        grad_v_pos = jnp.zeros_like(v)
        g_out_local = jnp.zeros_like(u_blk)   # positive grads by POSITION
        occ_ctx = jnp.zeros(t, jnp.float32)   # ctx occurrences by POSITION
        for j in range(offsets.shape[0]):     # 2W, unrolled in-trace
            o = int(offsets[j])
            u_o = jnp.roll(u_blk, -o, axis=0)  # row t -> w_out[block[t+o]]
            pmj = pm[:, j]                     # edge wraps masked by pm
            s = jnp.sum(v * u_o, axis=1)                             # (T,)
            g = (jax.nn.sigmoid(s) - 1.0) * pmj
            loss_pos += jnp.sum(jax.nn.log_sigmoid(s) * pmj)
            grad_v_pos += g[:, None] * u_o
            # the contribution of center t lands on context POSITION t+o
            g_out_local += jnp.roll(g[:, None] * v, o, axis=0)
            occ_ctx += jnp.roll(pmj, o)

        # each of a center's npairs pairs contributes the same shared-negative
        # term, so the negative loss scales by npairs
        n_terms = pm.sum() * (1 + negatives)
        npg = npairs.reshape(tg, G)
        loss = (-loss_pos
                - (jax.nn.log_sigmoid(-s_neg).sum(axis=2) * npg).sum()
                ) / jnp.maximum(n_terms, 1.0)

        # per-center shared-negative input gradient (both combine modes)
        neg_v = jnp.einsum("gck,gkd->gcd", g_neg, u_neg).reshape(t, -1)
        if combine == "sum":
            # per-occurrence SGD: each of a center's npairs pairs contributes
            # its own positive term AND its own copy of the shared-negative
            # term (see the loss scaling above); a stability bound below
            # clamps hot rows (duplicate steps land on the same stale weights)
            grad_v = grad_v_pos + npairs[:, None] * neg_v            # (T, D)
            grad_u_neg = jnp.einsum("gck,gcd,gc->gkd", g_neg, vg, npg)
            neg_occ = jnp.broadcast_to(npg.sum(axis=1)[:, None],
                                       (tg, negatives))
        else:
            # "mean": one bounded lr-step per row per batch (collapses on
            # long runs -- see _sgns_core comment)
            grad_v = (grad_v_pos / jnp.maximum(npairs, 1.0)[:, None]
                      + neg_v)                                       # (T, D)
            grad_u_neg = jnp.einsum("gck,gcd->gkd", g_neg, vg)       # (TG, K, D)
            neg_occ = jnp.broadcast_to(
                act_g.sum(axis=1)[:, None], (tg, negatives))

        # one combined out-row occurrence map; ctx occurrences arrive
        # pre-reduced by position, so the scalar scatter is T + K*T
        # entries instead of (2W+K)*T
        out_count = (jnp.zeros(w_out.shape[0], jnp.float32)
                     .at[blk_out_ids].add(occ_ctx)
                     .at[negs_id.reshape(-1)].add(neg_occ.reshape(-1)))
        if combine == "mean":
            in_count = jnp.zeros(
                w_in.shape[0], jnp.float32).at[centers_id].add(1.0)
            gin = grad_v / in_count[centers_id][:, None]
            denom = jnp.maximum(out_count, 1.0)
            g_out_local = g_out_local / denom[blk_out_ids][:, None]
            grad_u_neg = grad_u_neg / denom[negs_id][:, :, None]
        else:
            # stability bound: occurrence-units are pairs -- npairs per
            # center position, pm per positive out-entry, npairs per
            # negative out-entry (matching the gradient scaling above)
            cap = config.max_row_step
            in_scale = _row_step_scale(w_in.shape[0], centers_id, npairs,
                                       lr, cap)
            out_scale = _scale_from_count(out_count, lr, cap)
            gin = grad_v * in_scale[centers_id][:, None]
            g_out_local = g_out_local * out_scale[blk_out_ids][:, None]
            grad_u_neg = grad_u_neg * out_scale[negs_id][:, :, None]
        w_in = w_in.at[centers_id].add(-lr * gin)
        w_out = (w_out.at[blk_out_ids].add(-lr * g_out_local)
                 .at[negs_id].add(-lr * grad_u_neg))
        if with_pairs:
            return {"w_in": w_in, "w_out": w_out}, loss, pm.sum()
        return {"w_in": w_in, "w_out": w_out}, loss

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0,))


def make_corpus_train_step(config: Word2VecConfig, dictionary: Dictionary):
    """Scan-mode step: ONE device dispatch trains a whole (N, T) stack of
    token blocks via ``lax.scan`` — host interaction per N·T tokens drops to
    a single transfer + launch. step(params, key, blocks (N,T), lr) ->
    (params, mean_loss). This is the throughput path for benchmarking and for
    deployments where the corpus (or a shard of it) is staged in HBM."""
    block_step = make_block_train_step(config, dictionary, jit=False)

    def step(params, key, blocks, lr):
        def body(carry, block):
            params, key = carry
            key, sub = jax.random.split(key)
            params, loss = block_step(params, sub, block, lr)
            return (params, key), loss

        (params, _), losses = jax.lax.scan(body, (params, key), blocks)
        return params, losses.mean()

    return jax.jit(step, donate_argnums=(0,))


# -- host-side pair generation ----------------------------------------------

def subsample_block(block: np.ndarray, keep: np.ndarray,
                    rng: np.random.Generator) -> np.ndarray:
    return block[rng.random(len(block)) < keep[block]]


def generate_sg_pairs(block: np.ndarray, window: int,
                      rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """Dynamic-window skip-gram pairs, vectorized over offsets."""
    n = len(block)
    if n < 2:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    b = rng.integers(1, window + 1, size=n)
    centers, contexts = [], []
    for d in range(1, window + 1):
        ok = b >= d
        left = ok[d:]
        centers.append(block[d:][left])
        contexts.append(block[:-d][left])
        right = ok[:-d]
        centers.append(block[:-d][right])
        contexts.append(block[d:][right])
    return (np.concatenate(centers).astype(np.int32),
            np.concatenate(contexts).astype(np.int32))


def generate_cbow_batches(block: np.ndarray, window: int,
                          rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """(centers, context_block) with -1 padding outside the dynamic window."""
    n = len(block)
    if n < 2:
        return np.zeros(0, np.int32), np.zeros((0, 2 * window), np.int32)
    b = rng.integers(1, window + 1, size=n)
    ctx = np.full((n, 2 * window), -1, dtype=np.int32)
    for d in range(1, window + 1):
        ok = b >= d
        # left neighbor at distance d
        rows = np.arange(d, n)[ok[d:]]
        ctx[rows, window - d] = block[rows - d]
        rows = np.arange(0, n - d)[ok[:-d]]
        ctx[rows, window + d - 1] = block[rows + d]
    valid = (ctx >= 0).any(axis=1)
    return block[valid].astype(np.int32), ctx[valid]


# -- trainers ---------------------------------------------------------------

def save_embeddings(dictionary: Dictionary, embeddings: np.ndarray,
                    address: str, binary: bool = False) -> None:
    """Write embeddings in the word2vec interchange format the reference's
    ``SaveEmbedding`` produced (distributed_wordembedding.cpp:263-306):
    header ``"V D\\n"``, then per word either ``"word v1 … vD\\n"`` (text)
    or ``"word " + D float32 + "\\n"`` (binary, word2vec.c-compatible).
    ``address`` is a URI — any registered Stream scheme works."""
    from multiverso_tpu import io as mv_io
    emb = np.asarray(embeddings, np.float32)
    v = len(dictionary.words)
    if emb.shape[0] < v:
        log.fatal("save_embeddings: %d words but %d rows", v, emb.shape[0])
    with mv_io.get_stream(address, "w") as stream:
        stream.write(f"{v} {emb.shape[1]}\n".encode())
        for i, word in enumerate(dictionary.words):
            stream.write(word.encode() + b" ")
            if binary:
                stream.write(emb[i].tobytes() + b"\n")
            else:
                stream.write(" ".join(f"{x:g}" for x in emb[i]).encode()
                             + b"\n")


def load_embeddings(address: str, binary: bool = False
                    ) -> Tuple[list, np.ndarray]:
    """Inverse of :func:`save_embeddings`: returns (words, (V, D) matrix)."""
    from multiverso_tpu import io as mv_io
    with mv_io.get_stream(address, "r") as stream:
        data = stream.read()
    head, _, rest = data.partition(b"\n")
    v, dim = (int(x) for x in head.split())
    if v == 0:
        return [], np.zeros((0, dim), np.float32)
    words, rows = [], []
    pos = 0
    for _ in range(v):
        sp = rest.index(b" ", pos)
        words.append(rest[pos:sp].decode())
        if binary:
            vec = np.frombuffer(rest, np.float32, dim, sp + 1)
            pos = sp + 1 + 4 * dim + 1  # + trailing newline
        else:
            nl = rest.index(b"\n", sp)
            vec = np.array(rest[sp + 1:nl].split(), np.float32)
            pos = nl + 1
        rows.append(vec)
    return words, np.stack(rows)


def _decayed_lr(lr0: float, words_trained: int, total_words: int) -> float:
    """The reference's linear lr schedule (wordembedding.cpp:38-46):
    lr = lr0 * (1 - words_trained/(total+1)), floored at lr0 * 1e-4.
    Skipped under AdaGrad, like the reference."""
    frac = 1.0 - words_trained / (float(total_words) + 1.0)
    return lr0 * max(frac, 1e-4)


def _plan_blocks(blocks, epochs: int, total_words: Optional[int]):
    """Resolve a block plan for the epoch loops: ``blocks`` is either a
    materialized iterable (reused each epoch) or a zero-arg callable
    yielding a fresh stream per epoch (the reference re-read its train
    file per epoch rather than holding the corpus in RAM). Returns
    (per_epoch_fn, total_raw_words); streaming callers must supply
    ``total_words`` since the stream length is unknown up front."""
    if callable(blocks):
        if total_words is None:
            log.fatal("streaming blocks require total_words "
                      "(e.g. dictionary.counts.sum() * epochs)")
        return blocks, total_words
    blocks = list(blocks)
    if total_words is None:
        total_words = sum(len(b) for b in blocks) * epochs
    return (lambda: blocks), total_words


def _train_loop(trainer, blocks, epochs: int, log_every_s: float,
                label: str, total_words: Optional[int] = None,
                pipelined: bool = False, group: int = 1) -> None:
    """Shared epoch loop with throttled words/sec logging (the reference's
    ``Trainer::TrainIteration`` log shape) — used by both trainers. Applies
    the reference's linear lr decay over the planned word volume; decay
    progress counts RAW words fed (the reference counts words read before
    subsampling, wordembedding.cpp:38-46), so the schedule reaches its
    floor regardless of the subsample rate.

    ``pipelined`` drives trainers exposing submit_block/finish_block
    (the PS path): block i+1 is submitted before block i's completions
    are awaited, so each block's lr is one block stale — like the
    reference's asynchronously-shared word count.

    ``group`` coalesces that many consecutive blocks into one submission
    (pipelined mode): the per-submission fixed costs — candidate-set
    shaping, the packed upload, the fused dispatch (their size is not
    measured on the current machine) — amortize group-fold, while the
    kernel still chunks internally at ``batch_pairs`` granularity, so the
    update schedule per row is unchanged; only lr decay coarsens to the
    group."""
    t0 = time.time()
    last = t0
    per_epoch, total = _plan_blocks(blocks, epochs, total_words)
    decay = not getattr(trainer, "use_adagrad", False)
    seen = 0
    pending = None

    def grouped(it):
        buf = []
        for b in it:
            buf.append(b)
            if len(buf) >= group:
                yield np.concatenate(buf) if len(buf) > 1 else buf[0]
                buf = []
        if buf:
            yield np.concatenate(buf) if len(buf) > 1 else buf[0]

    for _ in range(epochs):
        for block in (grouped(per_epoch()) if pipelined and group > 1
                      else per_epoch()):
            lr = (_decayed_lr(trainer.config.lr, seen, total)
                  if decay else None)
            seen += len(block)
            if pipelined:
                nxt = trainer.submit_block(block, lr=lr)
                if pending is not None:
                    # loss stays on-device: fetching it here would put a
                    # full host round trip between block submissions
                    trainer.finish_block(pending, fetch_stats=False)
                pending = nxt
            else:
                trainer.train_block(block, lr=lr)
            now = time.time()
            if now - last > log_every_s:
                rate = trainer.words_trained / (now - t0)
                log.info("%sWords/sec: %.0fk  (trained %d)",
                         label, rate / 1e3, trainer.words_trained)
                last = now
    if pending is not None:
        trainer.finish_block(pending)

class DeviceTrainer:
    """HBM-resident training: embeddings live sharded on the mesh; the hot
    loop is host pair-gen → device step. Logs words/sec like the reference's
    ``Trainer::TrainIteration``."""

    def __init__(self, config: Word2VecConfig, dictionary: Dictionary,
                 mesh=None, use_block_step: Optional[bool] = None) -> None:
        self.config = config
        self.dictionary = dictionary
        self.params = init_params(config, mesh)
        if use_block_step is None:
            use_block_step = config.mode == "sg" and config.objective == "ns"
        self.use_block_step = use_block_step
        if use_block_step:
            self.block_step_fn = make_block_train_step(config, dictionary)
        else:
            self.step_fn = make_train_step(config, dictionary)
        self.key = jax.random.PRNGKey(config.seed)
        self.keep = dictionary.keep_probs(config.sample)
        self.rng = np.random.default_rng(config.seed)
        self.words_trained = 0

    def _batches(self, block: np.ndarray) -> Iterator[Dict[str, jnp.ndarray]]:
        """Fixed-shape (B,) batches; the tail is zero-padded with a
        ``pair_mask`` (consumed in-jit) rather than dropped, so blocks or
        corpora smaller than ``batch_pairs`` still train. Shapes stay
        static — one extra jit cache entry for masked batches."""
        bp = self.config.batch_pairs
        if self.config.mode == "sg":
            centers, other = generate_sg_pairs(block, self.config.window,
                                               self.rng)
            ctx_key = "contexts"
        else:
            centers, other = generate_cbow_batches(block, self.config.window,
                                                   self.rng)
            ctx_key = "context_block"
        for i in range(0, len(centers), bp):
            c, o = centers[i:i + bp], other[i:i + bp]
            if len(c) == bp:
                yield {"centers": jnp.asarray(c), ctx_key: jnp.asarray(o)}
            else:
                n = len(c)
                pad = ((0, bp - n),) + ((0, 0),) * (o.ndim - 1)
                yield {"centers": jnp.asarray(np.pad(c, (0, bp - n))),
                       ctx_key: jnp.asarray(np.pad(o, pad)),
                       "pair_mask": jnp.asarray(
                           (np.arange(bp) < n).astype(np.float32))}

    def train_block(self, block: np.ndarray, lr: Optional[float] = None) -> float:
        if self.config.sample > 0:  # sample=0 keeps everything: skip the draw
            block = subsample_block(block, self.keep, self.rng)
        lr = self.config.lr if lr is None else lr
        losses = []  # device values; sync ONCE at block end to keep steps pipelined
        if self.use_block_step:
            t = self.config.block_tokens
            for i in range(0, len(block), t):
                chunk = block[i:i + t]
                if len(chunk) < t:  # pad the tail; -1 tokens are masked in-jit
                    chunk = np.concatenate(
                        [chunk, np.full(t - len(chunk), -1, np.int32)])
                self.key, sub = jax.random.split(self.key)
                self.params, loss = self.block_step_fn(
                    self.params, sub, async_upload(chunk), lr)
                losses.append(loss)
        else:
            for batch in self._batches(block):
                self.key, sub = jax.random.split(self.key)
                self.params, loss = self.step_fn(self.params, sub, batch, lr)
                losses.append(loss)
        self.words_trained += len(block)
        return float(np.mean([float(l) for l in losses])) if losses else 0.0

    def train(self, blocks, epochs: int = 1, log_every_s: float = 10.0,
              total_words: Optional[int] = None) -> None:
        _train_loop(self, blocks, epochs, log_every_s, "",
                    total_words=total_words)
        jax.block_until_ready(self.params["w_in"])

    def embeddings(self) -> np.ndarray:
        return np.asarray(self.params["w_in"])[: self.config.vocab_size]


def host_negative_sampler(counts: np.ndarray, power: float = 0.75):
    """Host-side alias sampler over counts^0.75 — the PS client pre-draws its
    negatives so the candidate row set is known BEFORE the pull (the
    reference's client likewise knew its negative rows host-side via the
    unigram table; ``Applications/WordEmbedding/src/trainer.cpp``)."""
    from multiverso_tpu.ops.sampling import build_alias_table
    p = np.asarray(counts, dtype=np.float64) ** power
    thr, ali = build_alias_table(p)
    v = len(thr)

    def draw(rng: np.random.Generator, shape) -> np.ndarray:
        idx = rng.integers(0, v, size=shape)
        u = rng.random(shape)
        return np.where(u < thr[idx], idx, ali[idx]).astype(np.int32)

    return draw


def make_candidate_train_step(config: Word2VecConfig):
    """Compact-space block step for the PS client: ONE device dispatch trains
    a whole stack of minibatches whose ids are already remapped into the
    pulled candidate-row space.

    step(w_in_c, w_out_c, batches, lr) -> (w_in_c, w_out_c, loss_sum, mask_sum)
    where batches stacks N minibatches: in_ids/in_weights (N,B,C) and
    out_ids/labels/mask (N,B,T), ids compact (sentinel = last row). The scan
    keeps per-occurrence SGD semantics sequential ACROSS minibatches (like
    the reference's hot loop) while each minibatch is one MXU einsum set.
    """
    return jax.jit(_candidate_step_fn(config), donate_argnums=(0, 1))


def _candidate_step_fn(config: Word2VecConfig):
    combine = config.grad_combine
    cap = config.max_row_step

    def step(w_in_c, w_out_c, batches, lr):
        def body(carry, b):
            w_in, w_out = carry
            w_in, w_out, loss = _sgns_core(
                w_in, w_out, b["in_ids"], b["in_weights"], b["out_ids"],
                b["labels"], b["mask"], lr, combine, cap)
            return (w_in, w_out), (loss * jnp.maximum(b["mask"].sum(), 0.0),
                                   b["mask"].sum())
        (w_in_c, w_out_c), (losses, weights) = jax.lax.scan(
            body, (w_in_c, w_out_c), batches)
        return w_in_c, w_out_c, losses.sum(), weights.sum()

    return step


def make_candidate_delta_step(config: Word2VecConfig):
    """Device-path variant: consumes the HBM-resident gather buckets
    (bucket, padded_cols) directly and returns the PUSH PAYLOAD
    (delta · scale) instead of new weights. Everything host-expensive moves
    into the one dispatch: the col slice, the token→compact-slot remap
    (``searchsorted`` over the padded candidate ids — the same arrays the
    push needs anyway), the uint8→f32 label/mask casts (labels and mask
    cross the host boundary as bytes, quartering that transfer), the
    training scan, and the delta. Nothing aliases the caller's buffers
    after donation."""
    step = _candidate_step_fn(config)
    dim = config.dim
    # note: an on-device searchsorted remap was tried here (shipping raw
    # token ids) and LOST — 13.7k vs 27.9k words/s on the bench chip; the
    # binary search over a 131k-id bucket costs far more on the VPU than
    # the ~19ms numpy remap it replaced. The remap stays host-side.

    def dstep(cached_in, cached_out, batches, lr, scale):
        w_in = cached_in[:, :dim]
        w_out = cached_out[:, :dim]
        remapped = dict(batches,
                        labels=batches["labels"].astype(w_in.dtype),
                        mask=batches["mask"].astype(w_in.dtype))
        new_in, new_out, loss_sum, w_sum = step(w_in, w_out, remapped, lr)
        # one (2,) stats array: the caller fetches loss/weight in a SINGLE
        # blocking device→host round trip
        return ((new_in - w_in) * scale, (new_out - w_out) * scale,
                jnp.stack([loss_sum, w_sum]))

    return jax.jit(dstep, donate_argnums=(0, 1))


class PSTrainer:
    """Parameter-server client: embeddings live in MatrixTables; each block
    pulls ONLY its candidate rows, trains a compact local model in one scan
    dispatch, and pushes per-row deltas (or raw gradients when the server
    owns the optimizer).

    Reference capability (not copied): the 4-table AdaGrad recipe
    (``Applications/WordEmbedding/src/communicator.cpp:17-32``, table ids in
    ``constant.h:15-20``) with candidate-row ``RequestParameter`` pulls and
    all four mode×objective combinations
    (``distributed_wordembedding.cpp:147-252``).

    TPU-era re-design: the reference kept AdaGrad sum-gradient matrices as
    two EXTRA client-visible tables because its servers could only +=; here
    the server applies the optimizer (``updater_type="adagrad"`` tables own
    their accumulators in HBM), so the client pushes raw gradients and the
    two sum-gradient tables collapse into server updater state. Negatives
    (or Huffman path points) are pre-drawn host-side so the pull touches
    exactly the rows the block will train — no O(V) host transfer anywhere.
    """

    def __init__(self, config: Word2VecConfig, dictionary: Dictionary,
                 use_adagrad: bool = False) -> None:
        import multiverso_tpu as mv
        self.config = config
        self.dictionary = dictionary
        self.use_adagrad = bool(use_adagrad)
        v = config.vocab_size
        out_rows = v if config.objective == "ns" else max(v - 1, 1)
        updater = "adagrad" if self.use_adagrad else "default"
        # reference table ids 0..4: input, output, (2 sum-gradient tables —
        # subsumed by server updater state), wordcount
        self.input_table = mv.create_table(
            "matrix", v, config.dim, np.float32, updater_type=updater,
            init_range=(-0.5 / config.dim, 0.5 / config.dim), seed=config.seed)
        self.output_table = mv.create_table(
            "matrix", out_rows, config.dim, np.float32, updater_type=updater)
        self.count_table = mv.create_table("kv", np.int64)
        self.out_rows = out_rows
        if config.objective == "hs":
            self.huffman = HuffmanEncoder(dictionary.counts,
                                          config.max_code_length)
            self._hs_mask = self.huffman.mask()
        else:
            self.huffman = None
            self._neg_draw = host_negative_sampler(dictionary.counts)
        self.step_fn = make_candidate_train_step(config)
        self.delta_step_fn = make_candidate_delta_step(config)
        self.keep = dictionary.keep_probs(config.sample)
        self.rng = np.random.default_rng(config.seed)
        self.words_trained = 0
        self.last_block_stats: Dict[str, int] = {}
        # sg+ns fast path (device IO only): the roll-formulation block
        # kernel run directly on the compact candidate space -- one
        # training dispatch per block, an 8k-token host remap instead of a
        # per-pair one, and a 32KB block transfer instead of MB-scale pair
        # stacks. Negatives come from a fixed-size pool whose slot-alias
        # table preserves the unigram^0.75 marginal (see _submit_block_fast).
        self._fast_sgns = (config.mode == "sg" and config.objective == "ns")
        if self._fast_sgns:
            raw = make_block_train_step(config, dictionary, jit=False,
                                        neg_table=True)
            dim = config.dim

            def fast_delta(cached_in, cached_out, key, blocks_c, neg_slots,
                           lr, scale):
                w_in = cached_in[:, :dim]
                w_out = cached_out[:, :dim]

                def body(carry, blk):
                    params, key = carry
                    key, sub = jax.random.split(key)
                    params, loss, pairs = raw(params, sub, blk, lr,
                                              neg_slots, with_pairs=True)
                    return (params, key), (loss, pairs)

                (params, _), (losses, pairs) = jax.lax.scan(
                    body, ({"w_in": w_in, "w_out": w_out}, key), blocks_c)
                # pair-weighted: pad chunks (0 pairs, 0 loss) contribute
                # nothing, matching the pair path's weighted mean
                stats = jnp.stack([(losses * pairs).sum(), pairs.sum(),
                                   pairs.sum()])
                return ((params["w_in"] - w_in) * scale,
                        (params["w_out"] - w_out) * scale, stats)

            self._fast_delta_raw = fast_delta  # traceable, for the txn jit
            self._fast_delta_fn = jax.jit(fast_delta, donate_argnums=(0, 1))
            self._fast_key = jax.random.PRNGKey(config.seed + 1)
            self._fast_key_queue: list = []  # pre-split batch, see below
            self._txn_fn = None
            self._txn_name: Optional[str] = None
            # cap on the per-block negative pool (draw volume otherwise
            # tracks the old per-pair path: ~len(block)*window*negatives)
            self.neg_pool = 16384
            if self._can_transact():
                # build + REGISTER eagerly: under a multihost mesh a
                # replayed descriptor naming this program can arrive from
                # leader-origin traffic before this rank's first submit —
                # trainer construction is collective, so eager
                # registration on every rank closes that window
                self._build_txn_fn()

    # -- host-side batch shaping ---------------------------------------------
    def _block_pairs(self, block: np.ndarray):
        """(in_tok (P,C), in_w (P,C), predict (P,)) for this block's mode.
        in_tok may contain -1 (masked context slots)."""
        if self.config.mode == "sg":
            centers, contexts = generate_sg_pairs(
                block, self.config.window, self.rng)
            in_tok = centers[:, None]
            in_w = np.ones_like(in_tok, dtype=np.float32)
            return in_tok, in_w, contexts
        centers, ctx = generate_cbow_batches(block, self.config.window, self.rng)
        valid = (ctx >= 0).astype(np.float32)
        in_w = valid / np.maximum(valid.sum(1, keepdims=True), 1.0)
        return ctx, in_w, centers

    def _block_outputs(self, predict: np.ndarray):
        """(out_tok (P,T), labels (P,T), mask (P,T)); out_tok -1 where masked."""
        if self.config.objective == "ns":
            k = self.config.negatives
            negs = self._neg_draw(self.rng, (len(predict), k))
            out_tok = np.concatenate([predict[:, None], negs], axis=1)
            labels = np.zeros_like(out_tok, np.float32)
            labels[:, 0] = 1.0
            mask = np.ones_like(labels)
            return out_tok, labels, mask
        pts = self.huffman.points[predict]                   # (P, L)
        codes = self.huffman.codes[predict]
        mask = self._hs_mask[predict]
        out_tok = np.where(mask > 0, pts, -1).astype(np.int32)
        labels = (1.0 - codes).astype(np.float32) * mask
        return out_tok, labels, mask

    def train_block(self, block: np.ndarray,
                    lr: Optional[float] = None) -> float:
        pend = self.submit_block(block, lr)
        return self.finish_block(pend)

    def submit_block(self, block: np.ndarray,
                     lr: Optional[float] = None) -> Optional[Dict]:
        """Issue a block's pulls, training dispatch, and pushes WITHOUT
        waiting: the reference's pipeline mode overlapped exactly this —
        one thread prefetched the next block's rows while others trained
        (distributed_wordembedding.cpp:202-223). Returns a pending record
        for ``finish_block``; None when the block degenerates."""
        if self.config.sample > 0:  # sample=0 keeps everything: skip the draw
            block = subsample_block(block, self.keep, self.rng)
        if len(block) < 2:
            return None
        lr = self.config.lr if lr is None else lr
        if self._fast_sgns and (
                (getattr(self.input_table, "supports_device_io", False)
                 and getattr(self.output_table, "supports_device_io",
                             False))
                # multihost: device IO proper is off, but the NAMED fused
                # transaction rides the lockstep stream — the fast path's
                # txn branch is exactly that
                or self._can_transact()):
            return self._submit_block_fast(block, lr)
        in_tok, in_w, predict = self._block_pairs(block)
        if len(predict) == 0:
            return None
        out_tok, labels, mask = self._block_outputs(predict)

        # candidate sets: exactly the rows this block trains; both pulls are
        # issued before either is awaited so their round trips overlap (the
        # remote path pays one RTT, not two). In-process workers use the
        # DEVICE path: candidate rows are gathered in HBM and stay there —
        # the LocalForward analog; remote clients fall back to host arrays.
        in_cand = np.unique(in_tok[in_tok >= 0]).astype(np.int32)
        out_cand = np.unique(out_tok[out_tok >= 0]).astype(np.int32)
        device_io = (getattr(self.input_table, "supports_device_io", False)
                     and getattr(self.output_table, "supports_device_io",
                                 False))
        dim = self.config.dim
        n_in, n_out = len(in_cand), len(out_cand)
        if device_io:
            h_in = self.input_table.get_device_async(in_cand)
            h_out = self.output_table.get_device_async(out_cand)
            cached_in = self.input_table.wait_device(h_in, in_cand)
            cached_out = self.output_table.wait_device(h_out, out_cand)
            # the gather bucket IS the compact space: slots >= n are
            # sentinel copies (guaranteed >= 1 by the server's ensure_pad)
            r_in, r_out = cached_in.shape[0], cached_out.shape[0]
            sent_in, sent_out = n_in, n_out  # first pad slot
        else:
            h_in = self.input_table.get_async(in_cand)
            h_out = self.output_table.get_async(out_cand)
            cached_in = self.input_table.wait_get(h_in, in_cand)
            cached_out = self.output_table.wait_get(h_out, out_cand)
            # compact matrices: pow2 row buckets + a sentinel scratch row so
            # jit traces are reused across blocks of different candidate counts
            r_in = max(_next_pow2(n_in + 1), 8)
            r_out = max(_next_pow2(n_out + 1), 8)
            w_in_c = np.zeros((r_in, dim), np.float32)
            w_in_c[:n_in] = cached_in
            w_out_c = np.zeros((r_out, dim), np.float32)
            w_out_c[:n_out] = cached_out
            sent_in, sent_out = r_in - 1, r_out - 1

        # stack minibatches: pad pairs to a full (N, B, ...) block, N
        # bucketed to pow2 for trace reuse
        bp = self.config.batch_pairs
        p = len(predict)
        n = _next_pow2(-(-p // bp))
        def pad(arr, fill):
            flat = np.full((n * bp,) + arr.shape[1:], fill, arr.dtype)
            flat[:p] = arr
            return flat.reshape((n, bp) + arr.shape[1:])

        # token id → compact slot remap (host: measured faster than an
        # on-device searchsorted, see make_candidate_delta_step)
        in_ids = np.where(
            in_tok >= 0,
            np.searchsorted(in_cand, np.maximum(in_tok, 0)),
            sent_in).astype(np.int32)
        out_ids = np.where(
            out_tok >= 0,
            np.searchsorted(out_cand, np.maximum(out_tok, 0)),
            sent_out).astype(np.int32)
        batches_d = {
            "in_ids": jnp.asarray(pad(in_ids, sent_in)),
            "in_weights": jnp.asarray(pad(in_w, 0.0)),
            "out_ids": jnp.asarray(pad(out_ids, sent_out)),
        }

        if device_io:
            # ONE dispatch: col slice + training scan + delta·scale;
            # deltas never leave HBM and labels/mask cross as uint8.
            # Full-bucket push with sentinel-aimed pad ids (pad deltas are
            # exactly zero — masked grads carry zero weight), so shapes
            # stay static per pow2 bucket.
            batches_d["labels"] = jnp.asarray(pad(labels.astype(np.uint8), 0))
            batches_d["mask"] = jnp.asarray(pad(mask.astype(np.uint8), 0))
            sentinel = self.input_table.sentinel_row
            ids_in_p = np.concatenate(
                [in_cand, np.full(r_in - n_in, sentinel, np.int32)])
            sentinel_o = self.output_table.sentinel_row
            ids_out_p = np.concatenate(
                [out_cand, np.full(r_out - n_out, sentinel_o, np.int32)])
            scale = (-1.0 / lr) if self.use_adagrad else 1.0
            delta_in, delta_out, stats = self.delta_step_fn(
                cached_in, cached_out, batches_d, lr, scale)
            if self.use_adagrad:
                from multiverso_tpu.updaters import AddOption
                opt = AddOption(
                    worker_id=self.input_table._channel.worker_id(),
                    learning_rate=lr)
                a1 = self.input_table.add_device_async(delta_in, ids_in_p,
                                                       option=opt)
                a2 = self.output_table.add_device_async(delta_out, ids_out_p,
                                                        option=opt)
            else:
                a1 = self.input_table.add_device_async(delta_in, ids_in_p)
                a2 = self.output_table.add_device_async(delta_out, ids_out_p)
        else:
            # host path (remote proxies)
            batches_d["labels"] = jnp.asarray(pad(labels, 0.0))
            batches_d["mask"] = jnp.asarray(pad(mask, 0.0))
            new_in, new_out, loss_sum, w_sum = self.step_fn(
                jnp.asarray(w_in_c), jnp.asarray(w_out_c), batches_d, lr)
            new_in = np.asarray(new_in[:n_in])
            new_out = np.asarray(new_out[:n_out])
            delta_in = new_in - cached_in
            delta_out = new_out - cached_out
            if self.use_adagrad:
                # server owns the optimizer: ship the block's summed raw
                # gradient G ≈ -(delta)/lr; the adagrad updater applies
                # data -= lr·G/sqrt(g_sqr+rho) with HBM-resident accumulators
                from multiverso_tpu.updaters import AddOption
                opt = AddOption(
                    worker_id=self.input_table._channel.worker_id(),
                    learning_rate=lr)
                a1 = self.input_table.add_async(-delta_in / lr,
                                                row_ids=in_cand, option=opt)
                a2 = self.output_table.add_async(-delta_out / lr,
                                                 row_ids=out_cand, option=opt)
            else:
                a1 = self.input_table.add_async(delta_in, row_ids=in_cand)
                a2 = self.output_table.add_async(delta_out, row_ids=out_cand)
        if device_io:
            stats.copy_to_host_async()  # overlap the RTT with later work
        return {"a1": a1, "a2": a2, "stats": stats if device_io else None,
                "loss_sum": None if device_io else loss_sum,
                "w_sum": None if device_io else w_sum,
                "n_in": n_in, "n_out": n_out, "pairs": p,
                "block_len": int(len(block))}

    def _can_transact(self) -> bool:
        """Fused transactions need in-process tables (the fused jit reads
        the servers' device state) and an async-semantics server
        (BSP/deterministic keep per-table clocks a cross-table transaction
        cannot honor — those fall back to the staged pull/push path).
        Under a multihost mesh the NAMED form rides the lockstep stream
        (descriptor = program name + host args; every rank resolves its
        own identical jit), so cross-process worlds qualify too."""
        if (getattr(self.input_table, "_server_table", None) is None
                or getattr(self.output_table, "_server_table", None) is None):
            return False
        if not hasattr(self.input_table, "transact_device_async"):
            return False
        from multiverso_tpu.runtime.zoo import Zoo
        server = Zoo.instance().server
        return (getattr(server, "plain_async", False)
                or getattr(server, "supports_named_transact", False))

    def _build_txn_fn(self) -> None:
        """The whole PS block as one fused jit over both tables' device
        state: gather candidate rows, run the roll-formulation kernel,
        apply both tables' updates (linear scatter or server-side AdaGrad
        row update), return the stats scalar triple."""
        apply_in = self.input_table._server_table.row_apply_traceable()
        apply_out = self.output_table._server_table.row_apply_traceable()
        fast_delta = self._fast_delta_raw
        pc_in = self.input_table._server_table.padded_cols
        pc_out = self.output_table._server_table.padded_cols
        dim = self.config.dim

        def txn(datas, states, packed, key, lr, scale, worker, scalars,
                b_in, b_out, n_chunks, chunk):
            # `packed` is ONE int32 upload [ids_in | ids_out | blocks_c |
            # slot_alias] — four separate host->device transfers per block
            # would each pay the fixed per-transfer submission cost (not
            # measured on the current machine). The section sizes are
            # static (pow2-bucketed), so slicing is free at trace time.
            data_in, data_out = datas
            st_in, st_out = states
            ids_in = packed[:b_in]
            ids_out = packed[b_in:b_in + b_out]
            o = b_in + b_out
            blocks_c = packed[o:o + n_chunks * chunk].reshape(
                (n_chunks, chunk))
            slot_alias = packed[o + n_chunks * chunk:]
            d_in, d_out, stats = fast_delta(
                data_in[ids_in], data_out[ids_out], key, blocks_c,
                slot_alias, lr, scale)
            d_in = jnp.pad(d_in, ((0, 0), (0, pc_in - dim)))
            d_out = jnp.pad(d_out, ((0, 0), (0, pc_out - dim)))
            data_in, st_in = apply_in(data_in, st_in, ids_in, d_in,
                                      worker, scalars)
            data_out, st_out = apply_out(data_out, st_out, ids_out, d_out,
                                         worker, scalars)
            return [data_in, data_out], [st_in, st_out], stats

        self._txn_fn = jax.jit(txn, donate_argnums=(0, 1),
                               static_argnums=(8, 9, 10, 11))
        # name the program so the transaction can ride a multihost
        # lockstep descriptor: table ids are collective, so every rank
        # derives the same name for its identical locally-built jit
        from multiverso_tpu.runtime.programs import register_program
        self._txn_name = register_program(
            f"mv.w2v.block_txn/{self.input_table.table_id}"
            f"/{self.output_table.table_id}", self._txn_fn)

    def _submit_block_fast(self, block: np.ndarray, lr: float
                           ) -> Optional[Dict]:
        """sg+ns device fast path: run the roll-formulation block kernel
        directly on the compact candidate space.

        Layout: compact slot space = [unique block tokens | pool-only
        negative ids | sentinel pads]; the SAME slot numbering indexes the
        compact w_in and w_out buckets, so one 8k-token ``searchsorted``
        remap serves both sides. Negatives: ``neg_pool`` draws from the
        host unigram^0.75 sampler become a (P,) slot-alias table whose
        duplicate entries encode the marginal exactly -- the kernel draws
        uniform indices into it. Push ids are unique by construction
        (pool-only ids exclude block tokens), as the row-DMA scatter
        requires."""
        blk_u = np.unique(block).astype(np.int32)
        n_blk = len(blk_u)
        # pool sized to the block's negative demand (the per-pair path drew
        # ~pairs*K), pow2-bucketed so the kernel trace is shape-stable
        p_draws = _next_pow2(min(
            self.neg_pool,
            max(1024, len(block) * self.config.window
                * self.config.negatives)))
        draws = self._neg_draw(self.rng, (p_draws,)).reshape(-1)
        # vocab->compact-slot lookup table: O(touched) gathers replace
        # setdiff1d + three searchsorted calls (measured 3.7 ms/block of
        # host time at 8k-token blocks, the largest single submit cost
        # after the dispatch fusion). The lut is PERSISTENT — allocated
        # once and reset only at the touched entries each block, so the
        # cost stays O(touched), not O(vocab), at reference-scale (1e7)
        # vocabularies.
        lut = getattr(self, "_slot_lut", None)
        if lut is None:
            lut = self._slot_lut = np.full(self.config.vocab_size, -1,
                                           np.int32)
        # reset in ``finally``: the numpy allocations between fill and
        # reset can raise (MemoryError), and a dirty persistent lut would
        # silently map the next block's draws onto THIS block's slots
        pool_only = None
        try:
            lut[blk_u] = np.arange(n_blk, dtype=np.int32)
            pool_only = np.unique(draws[lut[draws] < 0]).astype(np.int32)
            lut[pool_only] = n_blk + np.arange(len(pool_only),
                                               dtype=np.int32)
            ids_out = np.concatenate([blk_u, pool_only])
            slot_alias = lut[draws]
            flat = lut[block]
        finally:
            lut[blk_u] = -1
            if pool_only is not None:
                lut[pool_only] = -1

        use_txn = self._can_transact()
        if not use_txn:
            h_in = self.input_table.get_device_async(blk_u)
            h_out = self.output_table.get_device_async(ids_out)
            cached_in = self.input_table.wait_device(h_in, blk_u)
            cached_out = self.output_table.wait_device(h_out, ids_out)

        # Chunk the block INSIDE the one scan dispatch at roughly the
        # pair path's update granularity (batch_pairs pairs ~ bp/window
        # tokens): the max_row_step stability clamp is per kernel step, so
        # hot rows move cap-per-chunk -- one whole-block step would clamp
        # them chunks-fold harder and visibly slow small-vocab learning.
        G = self.config.neg_sharing
        chunk = _next_pow2(max(128, self.config.batch_pairs
                               // max(self.config.window, 1)))
        chunk = min(chunk, _next_pow2(max(len(block), G)))
        if chunk % G:
            chunk *= G  # keep the grouped-negatives constraint
        n_chunks = _next_pow2(-(-len(block) // chunk))
        blocks_c = np.full((n_chunks, chunk), -1, np.int32)
        blocks_c.reshape(-1)[: len(block)] = flat  # lut-remapped above

        if not self._fast_key_queue:
            # one split dispatch per 64 blocks, not per block: every
            # dispatch has a fixed host submission cost (not measured on
            # the current machine)
            keys = jax.random.split(self._fast_key, 65)
            self._fast_key = keys[0]
            from multiverso_tpu.runtime.zoo import Zoo
            if Zoo.instance().multihost is not None:
                # multihost descriptors need HOST keys: one batched
                # readback per 64 blocks here, not a blocking per-block
                # device->host key fetch on the submit hot path
                self._fast_key_queue = list(np.asarray(keys[1:]))
            else:
                self._fast_key_queue = list(keys[1:])
        sub = self._fast_key_queue.pop()
        scale = (-1.0 / lr) if self.use_adagrad else 1.0

        if use_txn:
            # ONE dispatcher op, ONE device dispatch: gather both tables'
            # candidate rows, train, and apply both updates inside a
            # single fused jit over the tables' (donated) device state —
            # the 2-pull + kernel + 2-push staging collapses into one
            # dispatch submission
            if self._txn_fn is None:
                self._build_txn_fn()
            from multiverso_tpu.ops.pallas_rows import ROW_GROUP
            from multiverso_tpu.updaters import AddOption
            b_in = max(_next_pow2(n_blk + 1), ROW_GROUP)
            b_out = max(_next_pow2(len(ids_out) + 1), ROW_GROUP)
            ids_in_p = np.concatenate(
                [blk_u, np.full(b_in - n_blk,
                                self.input_table.sentinel_row, np.int32)])
            ids_out_p = np.concatenate(
                [ids_out, np.full(b_out - len(ids_out),
                                  self.output_table.sentinel_row,
                                  np.int32)])
            opt = AddOption(
                worker_id=self.input_table._channel.worker_id(),
                learning_rate=lr)
            from multiverso_tpu.runtime.zoo import Zoo
            packed_np = np.concatenate(
                [ids_in_p, ids_out_p, blocks_c.reshape(-1), slot_alias])
            if Zoo.instance().multihost is not None:
                # multihost descriptor: HOST args only (the jit converts
                # at trace/dispatch on every rank); same math as the
                # device consts below
                st = self.input_table._server_table
                worker = int(max(opt.worker_id, 0)
                             % max(1, st.num_workers))
                scalars = np.asarray(opt.scalars(), np.float32)
                packed, sub_arg = packed_np, np.asarray(sub)
            else:
                worker, scalars = (
                    self.input_table._server_table._option_consts(opt))
                packed, sub_arg = async_upload(packed_np), sub
            h = self.input_table.transact_device_async(
                self._txn_name, [self.output_table],
                args=(packed, sub_arg, lr, scale, worker, scalars,
                      b_in, b_out, blocks_c.shape[0], blocks_c.shape[1]))
            # the candidate gathers still happen (inside the fused jit) —
            # they just never leave HBM; keep the pull accounting so
            # "bytes ∝ candidate rows" stays observable
            self.input_table.rows_pulled += n_blk
            self.output_table.rows_pulled += len(ids_out)
            return {"txn": h, "block_len": len(block), "n_in": n_blk,
                    "n_out": len(ids_out), "pairs": -1, "stats": None}

        delta_in, delta_out, stats = self._fast_delta_fn(
            cached_in, cached_out, sub, async_upload(blocks_c),
            async_upload(slot_alias), lr, scale)

        sentinel_i = self.input_table.sentinel_row
        sentinel_o = self.output_table.sentinel_row
        r_in, r_out = cached_in.shape[0], cached_out.shape[0]
        ids_in_p = np.concatenate(
            [blk_u, np.full(r_in - n_blk, sentinel_i, np.int32)])
        ids_out_p = np.concatenate(
            [ids_out, np.full(r_out - len(ids_out), sentinel_o, np.int32)])
        if self.use_adagrad:
            from multiverso_tpu.updaters import AddOption
            opt = AddOption(
                worker_id=self.input_table._channel.worker_id(),
                learning_rate=lr)
            a1 = self.input_table.add_device_async(delta_in, ids_in_p,
                                                   option=opt)
            a2 = self.output_table.add_device_async(delta_out, ids_out_p,
                                                    option=opt)
        else:
            a1 = self.input_table.add_device_async(delta_in, ids_in_p)
            a2 = self.output_table.add_device_async(delta_out, ids_out_p)
        stats.copy_to_host_async()  # overlap the RTT with later work
        return {"a1": a1, "a2": a2, "stats": stats, "block_len": len(block),
                "n_in": n_blk, "n_out": len(ids_out), "pairs": -1}

    def finish_block(self, pend: Optional[Dict],
                     fetch_stats: bool = True) -> float:
        """Reclaim a submitted block's completions. ``fetch_stats=False``
        skips the loss materialization — that scalar fetch blocks the host
        until the block has run, serialized between block submissions,
        and the pipelined epoch loop only needs words/sec (host-side).
        The device stats stay retrievable via train_block's default
        fetching path."""
        if pend is None:
            return 0.0
        if "txn" in pend:
            # fused transaction: one completion carries the stats triple
            pend["stats"] = self.input_table.wait(pend["txn"])
            if fetch_stats and pend["stats"] is not None:
                # start the device->host copy before the count-table round
                # trip below so the two overlap
                pend["stats"].copy_to_host_async()
        else:
            # overlapped pushes; waits reclaim the completions
            self.input_table.wait(pend["a1"])
            self.output_table.wait(pend["a2"])
        self.count_table.add([0], [pend["block_len"]])
        self.words_trained += pend["block_len"]
        self.last_block_stats = {"in_rows": pend["n_in"],
                                 "out_rows": pend["n_out"],
                                 "pairs": pend["pairs"]}
        if not fetch_stats:
            return 0.0
        if pend["stats"] is not None:
            vals = np.asarray(pend["stats"])
            loss_sum, w_sum = vals[0], vals[1]
            if len(vals) > 2 and pend.get("pairs", -1) < 0:
                pend["pairs"] = int(vals[2])  # fast path: counted in-jit
                self.last_block_stats["pairs"] = pend["pairs"]
        else:
            loss_sum, w_sum = pend["loss_sum"], pend["w_sum"]
        return float(loss_sum) / max(float(w_sum), 1.0)

    def train(self, blocks, epochs: int = 1, log_every_s: float = 10.0,
              total_words: Optional[int] = None, group: int = 1) -> None:
        """Pipelined epoch loop: block i+1's host shaping + candidate pulls
        + dispatch are issued BEFORE block i's completions are awaited —
        the reference's pipeline mode (one thread prefetched the next
        block's rows while others trained,
        distributed_wordembedding.cpp:202-223), realized here as
        submit-ahead over the async table API instead of extra threads.
        ``group`` coalesces that many blocks per submission to amortize
        per-dispatch costs (see ``_train_loop``). Decay and logging live
        in ``_train_loop``."""
        _train_loop(self, blocks, epochs, log_every_s, "PS ",
                    total_words=total_words, pipelined=True, group=group)

    def embeddings(self) -> np.ndarray:
        return self.input_table.get()
