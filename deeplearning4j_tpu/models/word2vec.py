"""Word2Vec skip-gram — TPU-shaped.

Parity surface: ref models/word2vec/Word2Vec.java — fit() builds the vocab
(Huffman coding via Word2Vec.java:353), then trains skip-gram with
hierarchical softmax and/or negative sampling
(InMemoryLookupTable.iterate, InMemoryLookupTable.java:165-236), with
lr decay by words processed (:85) and frequent-word subsampling (:224).

TPU-first redesign (SURVEY.md §7 hard part (c)): the reference's hot loop is
a per-(word, tree-node) dot+axpy on 50-dim vectors — pure sequential BLAS-1.
Here training is *batched*: the host generates (center, context) skip-gram
pairs for a chunk of sentences; the device runs one jitted step per
fixed-size batch that
- gathers all embeddings for the batch,
- computes the closed-form SGNS / hierarchical-softmax gradients as one
  (B,K+1,D)-shaped einsum block on the MXU,
- applies updates with scatter-add (``.at[].add``), and
- samples negatives in-graph from the unigram^0.75 distribution.
Collisions between duplicate indices in one batch resolve by addition —
the same semantics as the reference's racy Hogwild updates.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.embeddings import (
    InMemoryLookupTable,
    cosine_nearest,
    cosine_sim,
)
from deeplearning4j_tpu.text.sentence_iterator import SentenceIterator
from deeplearning4j_tpu.text.tokenization import DefaultTokenizerFactory, TokenizerFactory
from deeplearning4j_tpu.text.vocab import VocabCache, build_huffman


# ------------------------------------------------------------ jitted steps ----

def _sgns_update(syn0, syn1neg, centers, contexts, weights, negs, lr):
    """Shared SGNS step body: gradient + collision-normalized scatter update.

    Collisions between duplicate indices normalize by the batch collision
    count: duplicate indices would otherwise SUM hundreds of same-row
    gradients computed at stale values (the reference applies them
    sequentially), which diverges on small vocabularies."""
    grad_v, u_idx, u_grad, u_w, loss = _sgns_grads(
        syn0, syn1neg, centers, contexts, weights, negs)
    c_cnt = jnp.zeros(syn0.shape[0], syn0.dtype).at[centers].add(weights)
    syn0 = syn0.at[centers].add(-lr * grad_v / jnp.maximum(c_cnt, 1.0)[centers, None])
    u_cnt = jnp.zeros(syn1neg.shape[0], syn0.dtype).at[u_idx].add(u_w)
    syn1neg = syn1neg.at[u_idx].add(
        -lr * u_grad / jnp.maximum(u_cnt, 1.0)[u_idx, None]
    )
    return syn0, syn1neg, loss


def _sgns_update_shared(syn0, syn1neg, ctr, ctx, wmat, negs_g, lr):
    """SGNS step on a skip-gram block with (a) negatives SHARED per group of
    P pairs and (b) WINDOW-REDUCED center rows. ctr: (block,) centers,
    ctx/wmat: (block, 2W) contexts + 0/1 validity, negs_g: (G, K) shared
    negatives for B = block*2W pairs.

    Why: the round-5 on-chip attribution measured the 4 scatter-adds as
    67-69% of the whole SGNS device epoch (noscatter ablation 0.164 s vs
    full 0.494 s at V=5k D=100; 2.8 s vs 9 s at V=50k D=256), and TPU
    scatter/gather cost is row-serialized — fewer rows is the only lever
    that matters. Two exact row reductions:

    - Shared negatives: drawing each group's K negatives once turns the
      negative gradients into per-group matmuls ("gpd,gkd->gpk" /
      "gpk,gpd->gkd") and shrinks the output-table scatter from B*(1+K) to
      B + G*K rows. This is the shared-memory word2vec batching recipe
      (pWord2Vec, Ji et al. 2016) — negatives still come from the same
      unigram^0.75 table, each pair still sees K negatives; they are just
      drawn per group instead of per pair (the 2015 reference draws per
      pair: Word2Vec.java:303-342 via sampleHolder).
    - Window reduction: a block's B pair-centers are its block positions
      each repeated 2W consecutive times, so the center table is gathered
      AND scattered at (block,) rows — the per-pair center matrix is a
      broadcast, and summing grad_v over the window before the scatter is
      bit-equivalent because the collision count is constant across a
      position's repeats.

    Measured at V=50k D=256 B=65540 (ablation scale): per-pair epoch
    ~27 ms/step, shared negatives 9.7 ms, shared+window 7.2 ms — net 3.7x
    (245k -> 908k words/s); at V=5k D=100 the shared epoch alone is 3.1x.

    Collision normalization matches _sgns_update: each updated row divides
    the SUM of its gradient contributions by the total contributing weight
    (a shared negative row's count is its group's total pair weight)."""
    block, two_w = ctx.shape
    vb = syn0[ctr]                          # (block,D) — the only c-gather
    v = jnp.repeat(vb, two_w, axis=0)       # (B,D) broadcast
    contexts = ctx.reshape(-1)
    weights = wmat.reshape(-1)
    centers = jnp.repeat(ctr, two_w)        # for the shared-grads contract
    grad_v, u_idx, u_grad, u_w, loss = _sgns_grads_shared(
        syn0, syn1neg, centers, contexts, weights, negs_g, v=v)

    wrow = wmat.sum(1)                                               # (block,)
    c_cnt = jnp.zeros(syn0.shape[0], syn0.dtype).at[ctr].add(wrow)
    gv_row = grad_v.reshape(block, two_w, -1).sum(1)
    syn0 = syn0.at[ctr].add(
        -lr * gv_row / jnp.maximum(c_cnt, 1.0)[ctr, None])
    u_cnt = jnp.zeros(syn1neg.shape[0], syn0.dtype).at[u_idx].add(u_w)
    syn1neg = syn1neg.at[u_idx].add(
        -lr * u_grad / jnp.maximum(u_cnt, 1.0)[u_idx, None])
    return syn0, syn1neg, loss


def neg_group_size(bsz: int, cap: int) -> int:
    """Largest divisor of the step's pair count ``bsz`` that is <= ``cap``
    (the shared update reshapes (B,) -> (G, P) so the group size must divide
    B; degrades to 1 — per-pair-equivalent semantics — when bsz is prime)."""
    return next(g for g in range(min(cap, bsz), 0, -1) if bsz % g == 0)


def build_neg_table(probs: np.ndarray, slots: int = 1 << 20) -> jnp.ndarray:
    """Device-resident inverse-CDF sampling table over unigram^0.75 probs
    (ref: the precomputed ``table`` in InMemoryLookupTable.java): slot t
    holds the word whose cumulative probability covers (t+0.5)/T."""
    probs = np.asarray(probs, np.float64)
    cum = np.cumsum(probs / probs.sum())
    return jnp.asarray(np.searchsorted(
        cum, (np.arange(slots) + 0.5) / slots).astype(np.int32))


def _sample_negs(key, neg_table, b: int, negative: int):
    """Negatives via a device-resident unigram^0.75 table gather — the exact
    posture of the reference's precomputed table (InMemoryLookupTable
    ``table`` field): O(1) per sample. The earlier jax.random.categorical
    materialized a (B, K, V) gumbel block PER STEP and argmax-reduced it —
    measured as the dominant cost of the whole SGNS scan on the chip."""
    slots = jax.random.randint(key, (b, negative), 0, neg_table.shape[0])
    return neg_table[slots]


@partial(jax.jit, static_argnames=("negative",), donate_argnums=(0, 1))
def _sgns_step(syn0, syn1neg, centers, contexts, weights, neg_table, lr, key,
               negative: int):
    """One negative-sampling step. centers/contexts: (B,), weights: (B,) 0/1
    mask for padding; neg_table: (T,) int32 unigram^0.75 sampling table."""
    negs = _sample_negs(key, neg_table, centers.shape[0], negative)
    return _sgns_update(syn0, syn1neg, centers, contexts, weights, negs, lr)


# ------------------------------------------------- device-side pair stream ----
#
# The reference walks sentence positions in Java and feeds dot/axpy updates
# (Word2Vec.java:303-342). Rounds 2-3 moved that walk to vectorized numpy on
# the host — but then every epoch ships the whole (center, context) pair
# stream host->device (~8 bytes/pair), which over a thin host link costs more
# than the compute (round 4 measured 6.7 MB/s against ~2 ms per 8k-pair step;
# the host link of a local chip: not measured).
# TPU-native fix: the *indexed corpus* is device-resident (uploaded once per
# vocab build, 4 bytes/word) and each epoch's subsampling draw, reduced-window
# draw, and skip-gram pair blocks are generated IN-GRAPH inside the same scan
# that runs the SGNS/HS updates — zero per-epoch host->device traffic.

def _pair_block(flatc, sidc, b, n_kept, pos0, block: int, window: int):
    """Skip-gram pairs for compacted-corpus positions [pos0, pos0+block).

    Returns centers (block,), contexts (block, 2W), weights (block, 2W);
    weights fold the reference's validity rules: in-corpus, same sentence,
    and |offset| <= b_center (the center's reduced window draw,
    ref Word2Vec.skipGram 'b' at Word2Vec.java:303-331)."""
    n = flatc.shape[0]
    w = window
    pos = pos0 + jnp.arange(block)
    posc = jnp.clip(pos, 0, n - 1)
    ctr = flatc[posc]
    offs = jnp.concatenate([jnp.arange(-w, 0), jnp.arange(1, w + 1)])  # (2W,)
    cpos = pos[:, None] + offs[None, :]
    in_bounds = (cpos >= 0) & (cpos < n_kept) & (pos[:, None] < n_kept)
    cposc = jnp.clip(cpos, 0, n - 1)
    ctx = flatc[cposc]
    same_sent = sidc[cposc] == sidc[posc][:, None]
    in_window = jnp.abs(offs)[None, :] <= b[posc][:, None]
    weights = (in_bounds & same_sent & in_window).astype(jnp.float32)
    return ctr, ctx, weights


def _epoch_setup(flat, sid, keep, key, window: int):
    """Per-epoch randomness, all in-graph: subsample draw + stable-sort
    compaction (kept words first, corpus order preserved — windows span
    removed words exactly like the reference, which deletes them from the
    sentence before windowing), plus the per-position reduced-window draw."""
    n = flat.shape[0]
    ka, kb = jax.random.split(key)
    keep_mask = jax.random.uniform(ka, (n,)) < keep[flat]
    n_kept = jnp.sum(keep_mask.astype(jnp.int32))
    order = jnp.argsort(jnp.where(keep_mask, 0, 1), stable=True)
    b = jax.random.randint(kb, (n,), 1, window + 1)
    return flat[order], sid[order], b, n_kept


@partial(jax.jit,
         static_argnames=("window", "negative", "block", "n_steps",
                          "neg_group"),
         donate_argnums=(0, 1))
def _sgns_device_epoch(syn0, syn1neg, flat, sid, keep, neg_table, lrs, key,
                       *, window: int, negative: int, block: int,
                       n_steps: int, neg_group: int = 0):
    """One WHOLE epoch in one dispatch: in-graph subsample + pair-gen + SGNS
    scan. Returns (syn0, syn1neg, losses, pairs_trained).

    ``neg_group``: pairs per shared-negative group (must divide the step's
    pair count; 0 = classic per-pair negatives) — see _sgns_update_shared."""
    kse, ksc = jax.random.split(key)
    flatc, sidc, b, n_kept = _epoch_setup(flat, sid, keep, kse, window)
    keys = jax.random.split(ksc, n_steps)
    bsz = block * 2 * window

    def body(carry, inp):
        syn0, syn1neg = carry
        step, lr, k = inp
        ctr, ctx, w = _pair_block(flatc, sidc, b, n_kept, step * block,
                                  block, window)
        if neg_group:
            negs_g = _sample_negs(k, neg_table, bsz // neg_group, negative)
            syn0, syn1neg, loss = _sgns_update_shared(
                syn0, syn1neg, ctr, ctx, w, negs_g, lr)
        else:
            c = jnp.broadcast_to(ctr[:, None], ctx.shape).reshape(-1)
            negs = _sample_negs(k, neg_table, bsz, negative)
            syn0, syn1neg, loss = _sgns_update(
                syn0, syn1neg, c, ctx.reshape(-1), w.reshape(-1), negs, lr)
        return (syn0, syn1neg), (loss, jnp.sum(w))

    (syn0, syn1neg), (losses, wsums) = jax.lax.scan(
        body, (syn0, syn1neg),
        (jnp.arange(n_steps), lrs, keys))
    return syn0, syn1neg, losses, jnp.sum(wsums)


@partial(jax.jit, static_argnames=("window", "block", "n_steps"),
         donate_argnums=(0, 1))
def _hs_device_epoch(syn0, syn1, flat, sid, keep, pts, cds, msk, lrs, key,
                     *, window: int, block: int, n_steps: int):
    """Hierarchical-softmax twin of _sgns_device_epoch."""
    flatc, sidc, b, n_kept = _epoch_setup(flat, sid, keep, key, window)

    def body(carry, inp):
        syn0, syn1 = carry
        step, lr = inp
        ctr, ctx, w = _pair_block(flatc, sidc, b, n_kept, step * block,
                                  block, window)
        c = jnp.broadcast_to(ctr[:, None], ctx.shape).reshape(-1)
        t = ctx.reshape(-1)
        syn0, syn1, loss = _hs_update(
            syn0, syn1, c, pts[t], cds[t], msk[t], w.reshape(-1), lr)
        return (syn0, syn1), (loss, jnp.sum(w))

    (syn0, syn1), (losses, wsums) = jax.lax.scan(
        body, (syn0, syn1), (jnp.arange(n_steps), lrs))
    return syn0, syn1, losses, jnp.sum(wsums)


def _hs_update(syn0, syn1, centers, points, codes, mask, weights, lr):
    """Shared HS step body (collision-normalized scatter update)."""
    v = syn0[centers]                       # (B,D)
    u = syn1[points]                        # (B,L,D)
    score = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", v, u))
    labels = 1.0 - codes
    g = (score - labels) * mask * weights[:, None]   # (B,L)

    grad_v = jnp.einsum("bl,bld->bd", g, u)
    grad_u = g[..., None] * v[:, None, :]

    c_cnt = jnp.zeros(syn0.shape[0], syn0.dtype).at[centers].add(weights)
    syn0 = syn0.at[centers].add(-lr * grad_v / jnp.maximum(c_cnt, 1.0)[centers, None])
    p_idx = points.reshape(-1)
    # collision counts weighted by the padding mask too — a padded row
    # (weight 0) must not inflate the denominator for its path nodes
    p_msk = (mask * weights[:, None]).reshape(-1)
    p_cnt = jnp.zeros(syn1.shape[0], syn0.dtype).at[p_idx].add(p_msk)
    syn1 = syn1.at[p_idx].add(
        -lr * grad_u.reshape(-1, grad_u.shape[-1])
        / jnp.maximum(p_cnt, 1.0)[p_idx, None]
    )
    eps = 1e-7
    loss = -jnp.sum(
        (labels * jnp.log(score + eps) + (1 - labels) * jnp.log(1 - score + eps))
        * mask * weights[:, None]
    )
    return syn0, syn1, loss


# ----------------------------------------------------- sharded (DP) steps ----

def _sgns_grads(syn0, syn1neg, centers, contexts, weights, negs):
    """Shared SGNS gradient math: returns (grad_v, u_idx, u_grad, u_w, loss).
    grad rows are pre-weighted by the 0/1 padding mask."""
    v = syn0[centers]                       # (B,D)
    u_pos = syn1neg[contexts]               # (B,D)
    u_neg = syn1neg[negs]                   # (B,K,D)
    negative = negs.shape[1]

    pos_score = jax.nn.sigmoid(jnp.sum(v * u_pos, axis=-1))          # (B,)
    neg_score = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", v, u_neg))   # (B,K)

    g_pos = (pos_score - 1.0) * weights                              # (B,)
    g_neg = neg_score * weights[:, None]                             # (B,K)

    grad_v = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    grad_u_pos = g_pos[:, None] * v
    grad_u_neg = g_neg[..., None] * v[:, None, :]

    u_idx = jnp.concatenate([contexts, negs.reshape(-1)])
    u_grad = jnp.concatenate(
        [grad_u_pos, grad_u_neg.reshape(-1, grad_u_neg.shape[-1])]
    )
    u_w = jnp.concatenate([weights, jnp.repeat(weights, negative)])
    eps = 1e-7
    loss = -(jnp.log(pos_score + eps) * weights).sum() - (
        jnp.log(1.0 - neg_score + eps) * weights[:, None]
    ).sum()
    return grad_v, u_idx, u_grad, u_w, loss


def _sgns_grads_shared(syn0, syn1neg, centers, contexts, weights, negs_g,
                       v=None):
    """Group-shared-negative twin of ``_sgns_grads`` (same return contract:
    grad_v, u_idx, u_grad, u_w, loss) for flat (B,) pairs with negs_g (G,K)
    shared per group of P = B/G pairs — the negative gradients become
    per-group matmuls and the u row count drops from B*(1+K) to B + G*K.

    ``v``: optional precomputed (B,D) center rows — the window-reduced
    caller (_sgns_update_shared) passes a (block,)-row gather broadcast
    over the window instead of a per-pair gather; omitted, the rows are
    gathered per pair (arbitrary pair streams, e.g. the sharded step)."""
    b = centers.shape[0]
    g, k = negs_g.shape
    p = b // g
    if v is None:
        v = syn0[centers]                   # (B,D)
    u_pos = syn1neg[contexts]               # (B,D)
    u_neg = syn1neg[negs_g]                 # (G,K,D)
    vg = v.reshape(g, p, -1)
    wg = weights.reshape(g, p)

    pos_score = jax.nn.sigmoid(jnp.sum(v * u_pos, axis=-1))          # (B,)
    neg_score = jax.nn.sigmoid(jnp.einsum("gpd,gkd->gpk", vg, u_neg))

    g_pos = (pos_score - 1.0) * weights                              # (B,)
    g_neg = neg_score * wg[..., None]                                # (G,P,K)

    grad_v = (g_pos[:, None] * u_pos
              + jnp.einsum("gpk,gkd->gpd", g_neg, u_neg).reshape(b, -1))
    grad_u_pos = g_pos[:, None] * v
    grad_u_neg = jnp.einsum("gpk,gpd->gkd", g_neg, vg)               # (G,K,D)

    u_idx = jnp.concatenate([contexts, negs_g.reshape(-1)])
    u_grad = jnp.concatenate([grad_u_pos, grad_u_neg.reshape(g * k, -1)])
    u_w = jnp.concatenate([
        weights,
        jnp.broadcast_to(wg.sum(1)[:, None], (g, k)).reshape(-1),
    ])
    eps = 1e-7
    loss = -(jnp.log(pos_score + eps) * weights).sum() - (
        jnp.log(1.0 - neg_score + eps) * wg[..., None]).sum()
    return grad_v, u_idx, u_grad, u_w, loss


def make_sharded_sgns_step(mesh, negative: int, neg_group: int = 0):
    """Data-parallel SGNS step over a device mesh.

    The pair stream is sharded on the mesh's data axis; each shard computes
    its scatter-added gradient contribution and collision counts, one psum
    AllReduces them over ICI, and every device applies the identical
    collision-normalized update — numerically the single-device ``_sgns_step``
    on the concatenated global batch (negatives are drawn per-shard).

    ``neg_group``: pairs per shared-negative group WITHIN each shard (must
    divide the per-shard pair count; 0 = classic per-pair draws) — the same
    scatter-row lever as the single-device epoch (_sgns_update_shared),
    applied to each shard's local gradient build before the psum.

    Replaces the reference's host-side delta-merging aggregation
    (ref: scaleout/perform/models/word2vec/Word2VecPerformer.java + spark
    dl4j-spark-nlp Word2VecPerformer) with in-graph collectives.
    """
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

    def step(syn0, syn1neg, centers, contexts, weights, neg_table, lr, key):
        shard = jax.lax.axis_index(DATA_AXIS)
        key = jax.random.fold_in(key, shard)
        b_local = centers.shape[0]
        if neg_group:
            negs_g = _sample_negs(key, neg_table, b_local // neg_group,
                                  negative)
            grad_v, u_idx, u_grad, u_w, loss = _sgns_grads_shared(
                syn0, syn1neg, centers, contexts, weights, negs_g)
        else:
            negs = _sample_negs(key, neg_table, b_local, negative)
            grad_v, u_idx, u_grad, u_w, loss = _sgns_grads(
                syn0, syn1neg, centers, contexts, weights, negs)
        g0 = jnp.zeros_like(syn0).at[centers].add(grad_v)
        c0 = jnp.zeros(syn0.shape[0], syn0.dtype).at[centers].add(weights)
        g1 = jnp.zeros_like(syn1neg).at[u_idx].add(u_grad)
        c1 = jnp.zeros(syn1neg.shape[0], syn0.dtype).at[u_idx].add(u_w)
        g0, c0, g1, c1, loss = jax.lax.psum((g0, c0, g1, c1, loss), DATA_AXIS)
        syn0 = syn0 - lr * g0 / jnp.maximum(c0, 1.0)[:, None]
        syn1neg = syn1neg - lr * g1 / jnp.maximum(c1, 1.0)[:, None]
        return syn0, syn1neg, loss

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


def make_sharded_hs_step(mesh):
    """Data-parallel hierarchical-softmax step (see make_sharded_sgns_step)."""
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

    def step(syn0, syn1, centers, points, codes, mask, weights, lr):
        v = syn0[centers]
        u = syn1[points]
        score = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", v, u))
        labels = 1.0 - codes
        g = (score - labels) * mask * weights[:, None]
        grad_v = jnp.einsum("bl,bld->bd", g, u)
        grad_u = g[..., None] * v[:, None, :]
        p_idx = points.reshape(-1)
        p_msk = mask.reshape(-1)
        g0 = jnp.zeros_like(syn0).at[centers].add(grad_v)
        c0 = jnp.zeros(syn0.shape[0], syn0.dtype).at[centers].add(weights)
        g1 = jnp.zeros_like(syn1).at[p_idx].add(
            grad_u.reshape(-1, grad_u.shape[-1]))
        c1 = jnp.zeros(syn1.shape[0], syn0.dtype).at[p_idx].add(p_msk)
        eps = 1e-7
        loss = -jnp.sum(
            (labels * jnp.log(score + eps) + (1 - labels) * jnp.log(1 - score + eps))
            * mask * weights[:, None]
        )
        g0, c0, g1, c1, loss = jax.lax.psum((g0, c0, g1, c1, loss), DATA_AXIS)
        syn0 = syn0 - lr * g0 / jnp.maximum(c0, 1.0)[:, None]
        syn1 = syn1 - lr * g1 / jnp.maximum(c1, 1.0)[:, None]
        return syn0, syn1, loss

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


# ----------------------------------------------------------------- model ----

class Word2Vec:
    def __init__(
        self,
        sentence_iterator: Optional[SentenceIterator] = None,
        tokenizer_factory: Optional[TokenizerFactory] = None,
        layer_size: int = 50,
        window: int = 5,
        min_word_frequency: int = 1,
        negative: int = 5,
        use_hierarchic_softmax: bool = False,
        lr: float = 0.025,
        min_lr: float = 1e-4,
        iterations: int = 1,
        sample: float = 1e-3,
        batch_size: int = 2048,
        seed: int = 123,
        mesh=None,
        shared_negatives: int = 25,
    ):
        self.sentence_iterator = sentence_iterator
        self.tokenizer_factory = tokenizer_factory or DefaultTokenizerFactory()
        self.layer_size = layer_size
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        if not use_hierarchic_softmax and negative <= 0:
            raise ValueError("need negative sampling and/or hierarchical softmax")
        self.lr = lr
        self.min_lr = min_lr
        self.iterations = iterations
        self.sample = sample
        self.batch_size = batch_size
        self.seed = seed
        # pairs per shared-negative group on the device-epoch path (0 =
        # classic per-pair draws, the reference's posture); sharing is the
        # scatter-row lever that makes the epoch matmul-bound — see
        # _sgns_update_shared for the measured 3.1x and the citation
        self.shared_negatives = shared_negatives
        # data-parallel training: pair batches shard across the mesh's data
        # axis, embedding updates AllReduce in-graph (make_sharded_sgns_step)
        self.mesh = mesh
        if mesh is not None:
            from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

            d = mesh.shape[DATA_AXIS]
            if self.batch_size % d:
                self.batch_size += d - self.batch_size % d  # round up to shard evenly
        self.vocab = VocabCache()
        self._lookup_table: Optional[InMemoryLookupTable] = None
        self.total_words_trained = 0
        self.last_fit_timings: dict = {}
        self._flat = np.zeros(0, np.int32)  # cached indexed corpus
        self._sid = np.zeros(0, np.int32)
        self._corpus_dev = None  # device-resident copy, uploaded once
        # Device-resident embeddings carried across fit() calls — the DEVICE
        # copy is authoritative after training and the host table syncs
        # LAZILY on first read (``lookup_table`` property): a fit() never
        # pays the table download (measured round 5: the download WAS the
        # entire "device drain" at 50k x 256 — 2 x 51 MB device->host),
        # continued training never re-uploads, and readers still always see
        # trained values. ``_host_digest`` records the host arrays' content
        # at the last sync/upload so an external write to the host table
        # between fits is detected and wins (it re-uploads).
        self._syn_dev = None
        self._host_digest = None
        self._table_stale = False  # True: device ahead of host table
        self._neg_table_dev = None   # unigram^0.75 table, uploaded once
        self._hs_tabs_dev = None     # Huffman path tables, uploaded once

    def block_until_ready(self) -> None:
        """Timing fence: block until all pending device-side training on the
        embedding tables has completed (without downloading them — reading
        ``lookup_table`` does that). Benches must call this before stopping
        a clock around fit()."""
        if self._syn_dev is not None:
            jax.block_until_ready(self._syn_dev)

    @property
    def lookup_table(self) -> Optional[InMemoryLookupTable]:
        """The host-side embedding table (ref: Word2Vec.lookupTable). Reading
        it syncs any pending device-side training first."""
        if self._table_stale:
            self._download_table()
        return self._lookup_table

    @lookup_table.setter
    def lookup_table(self, table: Optional[InMemoryLookupTable]) -> None:
        self._lookup_table = table
        self._table_stale = False
        self._syn_dev = None
        self._host_digest = None

    def _download_table(self) -> None:
        table = self._lookup_table
        syn0, syn1, syn1neg = self._syn_dev
        # download only what the objective trained — syn1 is untouched
        # without HS, syn1neg untouched without negative sampling, and each
        # matrix costs a full device->host transfer of the embedding table
        table.syn0 = np.asarray(syn0)
        if self.use_hs:
            table.syn1 = np.asarray(syn1)
        if self.negative > 0:
            table.syn1neg = np.asarray(syn1neg)
        self._table_stale = False
        self._host_digest = self._digest(
            (table.syn0, table.syn1, table.syn1neg))

    # ---- vocab ----
    def build_vocab(self) -> None:
        """Tokenize all sentences, count, prune, Huffman-code
        (ref: Word2Vec.fit vocab phase + Huffman.java).

        The tokenized corpus is kept (as token lists) and indexed ONCE into
        flat vocab-index arrays — round 2 re-tokenized the whole corpus every
        epoch in a Python loop, starving the device at corpus scale
        (VERDICT r02 weak #7)."""
        assert self.sentence_iterator is not None, "no sentence iterator configured"
        # When the native fast path is even possible (cheap non-consuming
        # guards), materialize the corpus ONCE and feed the same list to both
        # the native attempt and the fallback — a one-shot (non-resettable)
        # iterable can never be half-consumed by a native attempt that then
        # bails (e.g. on non-ASCII text). When it is impossible, stream the
        # iterator directly: no memory spent on a list nobody joins.
        native = None
        if self._native_path_possible():
            sentences = list(self.sentence_iterator)
            native = self._native_vocab_index(sentences)
        else:
            sentences = self.sentence_iterator
        if native is not None:
            words, counts, self._flat, self._sid = native
            for w, c in zip(words, counts):
                self.vocab.add_token(w, by=int(c))
            self.vocab.finish(self.min_word_frequency)
        else:
            corpus_tokens: List[List[str]] = []
            for sentence in sentences:
                toks = self.tokenizer_factory.create(sentence).get_tokens()
                corpus_tokens.append(toks)
                for tok in toks:
                    self.vocab.add_token(tok)
            self.vocab.finish(self.min_word_frequency)
            # index the cached corpus: one flat array + sentence ids
            index_of = self.vocab.index_of
            sents = []
            for toks in corpus_tokens:
                idx = np.array(
                    [i for i in (index_of(t) for t in toks) if i >= 0],
                    dtype=np.int32)
                if idx.size >= 2:
                    sents.append(idx)
            if sents:
                self._flat = np.concatenate(sents)
                self._sid = np.repeat(np.arange(len(sents), dtype=np.int32),
                                      [s.size for s in sents])
            else:
                self._flat = np.zeros(0, np.int32)
                self._sid = np.zeros(0, np.int32)
        build_huffman(self.vocab)
        self.lookup_table = InMemoryLookupTable(
            self.vocab, self.layer_size, seed=self.seed,
            use_hs=self.use_hs, negative=self.negative,
        )
        self._corpus_dev = None   # new corpus index → re-upload on next fit
        self._neg_table_dev = None  # vocab changed → rebuild sampling tables
        self._hs_tabs_dev = None
        # (the lookup_table setter above already dropped the old-vocab
        # device embeddings and digest)

    def _native_path_possible(self) -> bool:
        """Non-consuming preconditions for the C++ vocab path: plain
        whitespace tokenizer with no pre-processor, a fresh vocab, and the
        native library present. None of these touch the sentence iterator,
        so build_vocab checks them BEFORE deciding whether to materialize
        the corpus for the native join."""
        from deeplearning4j_tpu.native.lib import native_available
        from deeplearning4j_tpu.text.tokenization import DefaultTokenizerFactory

        if type(self.tokenizer_factory) is not DefaultTokenizerFactory:
            return False
        if self.tokenizer_factory.pre_processor is not None:
            return False
        if not self.vocab.is_empty():
            return False  # accumulating into an existing vocab: python path
        return native_available()

    def _native_vocab_index(self, sentences=None):
        """C++ tokenize+count+index fast path (native/text.cpp via
        native/lib.py corpus_index) — the host-side vocab-build hot path the
        reference runs on a JVM actor pool (Word2Vec.java vocab phase +
        VocabActor). Applies only when it is PROVABLY equivalent to the
        Python path (see _native_path_possible, plus ASCII text: byte-wise
        split/sort == str semantics); returns None otherwise and the Python
        path runs. ``sentences`` is the materialized corpus from build_vocab
        — the same list the fallback reads, so bailing out here never costs
        the caller its iterator (defaults to the configured iterator for
        direct probing in tests)."""
        from deeplearning4j_tpu.native.lib import corpus_index

        if not self._native_path_possible():
            return None
        if sentences is None:
            sentences = self.sentence_iterator
        try:
            text = "\n".join(
                s.replace("\n", " ") for s in sentences
            ).encode("utf-8", errors="strict")
        except UnicodeEncodeError:
            return None
        out = corpus_index(text, self.min_word_frequency)
        if out is None:
            return None
        words, counts, flat, sids = out
        return words, counts, flat, sids

    @staticmethod
    def _digest(arrays) -> tuple:
        """Cheap content fingerprint of the embedding tables (sha1 over raw
        bytes + shapes) — equality means the host tables are unchanged since
        the last download, so the device copies can be reused."""
        import hashlib

        h = hashlib.sha1()
        shapes = []
        for a in arrays:
            a = np.ascontiguousarray(a)
            shapes.append(a.shape)
            h.update(a.tobytes())
        return (h.hexdigest(), tuple(shapes))

    # ---- pair generation (host side) ----
    def _keep_probs(self) -> np.ndarray:
        """Subsampling keep-probability per word (ref: Word2Vec.java:224)."""
        counts = self.vocab.counts()
        if self.sample <= 0:
            return np.ones_like(counts, dtype=np.float64)
        freq = counts / max(self.vocab.total_word_count(), 1)
        return np.minimum(1.0, np.sqrt(self.sample / np.maximum(freq, 1e-12)))

    def _skipgram_pairs(self, sents: Sequence[np.ndarray],
                        rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized (center, context) generation: all sentences flattened
        into one array, one shifted-mask pass per window offset — no
        per-position Python loop (the reference walks positions in Java,
        Word2Vec.java:303-331; at corpus scale a Python transliteration of
        that loop starves the device)."""
        if not sents:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        flat = np.concatenate(sents).astype(np.int32)
        sid = np.repeat(np.arange(len(sents)), [s.size for s in sents])
        return self._pairs_from_flat(flat, sid, rng)

    def _pairs_from_flat(self, flat: np.ndarray, sid: np.ndarray,
                         rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
        if flat.size < 2:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        # random reduced window per position (word2vec/ref behavior)
        b = rng.integers(1, self.window + 1, size=flat.size)
        centers: List[np.ndarray] = []
        contexts: List[np.ndarray] = []
        for d in range(1, self.window + 1):
            same = sid[:-d] == sid[d:]  # positions i, i+d in the same sentence
            fwd = same & (b[:-d] >= d)   # i's window reaches i+d
            bwd = same & (b[d:] >= d)    # (i+d)'s window reaches i
            centers.append(flat[:-d][fwd])
            contexts.append(flat[d:][fwd])
            centers.append(flat[d:][bwd])
            contexts.append(flat[:-d][bwd])
        # pairs come out grouped by offset rather than corpus order; the
        # caller shuffles pairs at epoch level, so SGD statistics are the same
        return np.concatenate(centers), np.concatenate(contexts)

    def _subsampled_flat(self, rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
        """Per-epoch frequent-word subsampling, vectorized over the cached
        corpus index (ref: Word2Vec.java:224)."""
        flat, sid = self._flat, self._sid
        if self.sample > 0 and flat.size:
            keep = self._keep_probs()
            m = rng.random(flat.size) < keep[flat]
            flat, sid = flat[m], sid[m]
        return flat, sid

    def _neg_table(self):
        """Device-resident sampling table, built once per vocab (each build
        is a float64 cumsum over 1M slots plus a 4 MB upload — per-fit
        rebuilds would charge that to every continued-training call)."""
        if self._neg_table_dev is None:
            self._neg_table_dev = build_neg_table(
                self._lookup_table.unigram_probs())
        return self._neg_table_dev

    def _huffman_tables(self):
        """Padded Huffman path matrices (V, L) for the HS objective,
        device-resident, built once per vocab."""
        if self._hs_tabs_dev is not None:
            return self._hs_tabs_dev
        max_len = max((len(w.code) for w in self.vocab.words()), default=1)
        n = self.vocab.num_words()
        pts = np.zeros((n, max_len), np.int32)
        cds = np.zeros((n, max_len), np.float32)
        msk = np.zeros((n, max_len), np.float32)
        for w in self.vocab.words():
            path_len = len(w.code)
            pts[w.index, :path_len] = w.points
            cds[w.index, :path_len] = w.code
            msk[w.index, :path_len] = 1.0
        self._hs_tabs_dev = (jnp.asarray(pts), jnp.asarray(cds), jnp.asarray(msk))
        return self._hs_tabs_dev

    # ---- training ----
    def fit(self) -> None:
        """Train. Fills ``last_fit_timings`` with the host-vs-device split:
        host_pairgen_s (host-side numpy pair generation — 0 on the
        single-device path, where pairs are generated in-graph),
        host_batch_prep_s (uploads + dispatch enqueue), device_drain_s (time
        blocked fetching the final embeddings — device work not already
        overlapped with host prep), total_s, n_pairs, n_dispatches."""
        import time as _time

        if self._lookup_table is None:
            self.build_vocab()
        table = self._lookup_table  # raw: a stale host table must NOT sync
        key = jax.random.PRNGKey(self.seed)
        t_fit0 = _time.perf_counter()
        self._timings = {"pairgen": 0.0, "prep": 0.0, "dispatches": 0}

        # reuse the previous fit's device-resident embeddings when the host
        # table still matches the content we last synced/uploaded (each
        # re-upload is a full embedding-table host->device transfer); any
        # external change — serializer load, reset_weights, in-place edit —
        # falls back to a fresh upload of the host arrays. Change detection
        # is by content digest, not a retained host copy: at 1M-vocab the
        # three tables are ~400 MB each and a full duplicate would double
        # host memory for a 20-byte check.
        cur = (table.syn0, table.syn1, table.syn1neg)
        if self._syn_dev is not None and self._host_digest is not None and (
            self._digest(cur) == self._host_digest
        ):
            syn0, syn1, syn1neg = self._syn_dev
        else:
            syn0, syn1, syn1neg = (jnp.asarray(a) for a in cur)
            self._host_digest = self._digest(cur)
        # the arrays are donated into the epoch program below: from here any
        # failure loses un-synced device training (same durability contract
        # as a crashed in-memory trainer); the table must come back READABLE
        # either way, so on failure the host table — content as of the last
        # sync/upload — becomes authoritative again
        self._syn_dev = None
        self._table_stale = False
        if self.mesh is None:
            syn0, syn1, syn1neg, pairs_seen = self._fit_device(
                syn0, syn1, syn1neg, key, _time)
        else:
            syn0, syn1, syn1neg, pairs_seen = self._fit_host_pairs(
                syn0, syn1, syn1neg, key, _time)

        t0 = _time.perf_counter()
        pairs_seen = int(pairs_seen)  # device scalar fetch: drains the queue
        # the trained tables STAY on device; the host table syncs lazily on
        # the first lookup_table read (round 5: at 50k-vocab x 256 the
        # download was 2 x 51 MB and dominated every fit)
        self._syn_dev = (syn0, syn1, syn1neg)
        self._table_stale = True
        # freeze the now-stale host arrays: an in-place write through a
        # retained reference would bypass the property's sync and silently
        # shadow the device-side training — make it fail loudly instead
        # (post-sync arrays are read-only jax views already; wholesale
        # re-assignment remains the supported external-edit path)
        for arr in (table.syn0, table.syn1, table.syn1neg):
            if isinstance(arr, np.ndarray) and arr.flags.owndata:
                arr.flags.writeable = False
        t_drain = _time.perf_counter() - t0
        self.last_fit_timings = {
            "host_pairgen_s": round(self._timings["pairgen"], 4),
            "host_batch_prep_s": round(self._timings["prep"], 4),
            "device_drain_s": round(t_drain, 4),
            "total_s": round(_time.perf_counter() - t_fit0, 4),
            "n_pairs": pairs_seen,
            "n_dispatches": self._timings["dispatches"],
        }
        self.total_words_trained = pairs_seen

    def _fit_device(self, syn0, syn1, syn1neg, key, _time):
        """Single-device training: the WHOLE epoch — subsampling draw,
        reduced-window draw, skip-gram pair blocks, SGNS/HS updates — runs as
        one jitted scan per epoch on the device-resident corpus index
        (_pair_block/_sgns_device_epoch). Per-epoch host->device traffic is a
        PRNG key and a (n_steps,) lr schedule; the corpus uploads once per
        vocab build. Replaces rounds 2-3's host pair stream, which shipped
        ~8 bytes/pair every epoch and was transfer-bound through thin links."""
        n = int(self._flat.size)
        if n < 2:
            return syn0, syn1, syn1neg, 0
        t0 = _time.perf_counter()
        if self._corpus_dev is None:
            self._corpus_dev = (jnp.asarray(self._flat), jnp.asarray(self._sid))
        flat_d, sid_d = self._corpus_dev
        keep_d = jnp.asarray(self._keep_probs().astype(np.float32))
        neg_table = self._neg_table() if self.negative > 0 else None
        hs_tabs = self._huffman_tables() if self.use_hs else None
        window = self.window
        block = max(-(-self.batch_size // (2 * window)), 1)
        n_steps = -(-n // block)
        iters = max(self.iterations, 1)
        bsz = block * 2 * window
        neg_group = 0
        if self.shared_negatives and self.negative > 0:
            neg_group = neg_group_size(bsz, self.shared_negatives)
        self._timings["prep"] += _time.perf_counter() - t0  # graftlint: allow[untimed-dispatch] host-phase split timer; device share is measured separately as drain

        pairs_total = None
        for e in range(iters):
            t0 = _time.perf_counter()
            # linear lr decay by corpus-position fraction — the device-side
            # equivalent of the reference's words-processed decay
            # (Word2Vec.java:85); positions ARE words here
            frac = (e * n + np.arange(n_steps) * block) / max(n * iters, 1)
            lrs = np.maximum(self.min_lr,
                             self.lr * (1.0 - np.minimum(frac, 1.0))
                             ).astype(np.float32)
            lrs_j = jnp.asarray(lrs)
            self._timings["prep"] += _time.perf_counter() - t0  # graftlint: allow[untimed-dispatch] host-phase split timer; device share is measured separately as drain
            if self.negative > 0:
                key, sub = jax.random.split(key)
                syn0, syn1neg, _, wtot = _sgns_device_epoch(
                    syn0, syn1neg, flat_d, sid_d, keep_d, neg_table, lrs_j,
                    sub, window=window, negative=self.negative, block=block,
                    n_steps=n_steps, neg_group=neg_group)
                self._timings["dispatches"] += 1
            if self.use_hs:
                key, sub = jax.random.split(key)
                syn0, syn1, _, wtot = _hs_device_epoch(
                    syn0, syn1, flat_d, sid_d, keep_d, *hs_tabs, lrs_j, sub,
                    window=window, block=block, n_steps=n_steps)
                self._timings["dispatches"] += 1
            pairs_total = wtot if pairs_total is None else pairs_total + wtot
        return syn0, syn1, syn1neg, (0 if pairs_total is None else pairs_total)

    def _fit_host_pairs(self, syn0, syn1, syn1neg, key, _time):
        """Mesh-sharded training: host-side vectorized pair generation, pair
        batches sharded over the mesh's data axis, in-graph psum aggregation
        (make_sharded_sgns_step). The host pair stream stays here because
        shard_map needs explicitly sharded batch inputs."""
        rng = np.random.default_rng(self.seed)
        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

        b_local = self.batch_size // self.mesh.shape[DATA_AXIS]
        ng = (neg_group_size(b_local, self.shared_negatives)
              if (self.shared_negatives and self.negative > 0 and b_local)
              else 0)
        sgns_step = make_sharded_sgns_step(self.mesh, self.negative,
                                           neg_group=ng)
        hs_step = make_sharded_hs_step(self.mesh)
        neg_table = self._neg_table() if self.negative > 0 else None
        if self.use_hs:
            pts_j, cds_j, msk_j = self._huffman_tables()

        total_pairs = None  # set from the first epoch's pair count so the
        pairs_seen = 0      # linear decay spans the whole run in PAIR units
        bsz = self.batch_size

        for _ in range(max(self.iterations, 1)):
            t0 = _time.perf_counter()
            flat, sid = self._subsampled_flat(rng)
            centers, contexts = self._pairs_from_flat(flat, sid, rng)
            n_pairs = centers.shape[0]
            if n_pairs:
                perm = rng.permutation(n_pairs)
                centers, contexts = centers[perm], contexts[perm]
            self._timings["pairgen"] += _time.perf_counter() - t0  # graftlint: allow[untimed-dispatch] host-phase split timer; device share is measured separately as drain
            if total_pairs is None:
                total_pairs = max(n_pairs, 1) * max(self.iterations, 1)

            for start in range(0, max(n_pairs, 1), bsz):
                t0 = _time.perf_counter()
                c = centers[start : start + bsz]
                t = contexts[start : start + bsz]
                n_real = c.shape[0]
                if n_real == 0:
                    break
                w = np.ones(n_real, np.float32)
                if n_real < bsz:  # pad the tail, mask the padding
                    pad = bsz - n_real
                    c = np.concatenate([c, np.zeros(pad, np.int32)])
                    t = np.concatenate([t, np.zeros(pad, np.int32)])
                    w = np.concatenate([w, np.zeros(pad, np.float32)])
                frac = min(pairs_seen / max(total_pairs, 1), 1.0)
                lr = max(self.min_lr, self.lr * (1.0 - frac))
                cj, tj, wj = jnp.asarray(c), jnp.asarray(t), jnp.asarray(w)
                if self.negative > 0:
                    key, sub = jax.random.split(key)
                    syn0, syn1neg, _ = sgns_step(
                        syn0, syn1neg, cj, tj, wj, neg_table,
                        jnp.float32(lr), sub,
                    )
                if self.use_hs:
                    syn0, syn1, _ = hs_step(
                        syn0, syn1, cj, pts_j[tj], cds_j[tj], msk_j[tj], wj,
                        jnp.float32(lr),
                    )
                pairs_seen += n_real
                self._timings["prep"] += _time.perf_counter() - t0  # graftlint: allow[untimed-dispatch] host-phase split timer; device share is measured separately as drain
                self._timings["dispatches"] += 1
        return syn0, syn1, syn1neg, pairs_seen

    # ---- query API (ref: WordVectors interface) ----
    def word_vector(self, word: str) -> Optional[np.ndarray]:
        return self.lookup_table.vector(word) if self.lookup_table else None

    def has_word(self, word: str) -> bool:
        return self.vocab.contains(word)

    def similarity(self, w1: str, w2: str) -> float:
        return cosine_sim(self.word_vector(w1), self.word_vector(w2))

    def words_nearest(self, word: str, n: int = 10) -> List[str]:
        v = self.word_vector(word)
        if v is None:
            return []
        idx = cosine_nearest(self.lookup_table.syn0, v, n,
                             exclude=self.vocab.index_of(word))
        return [self.vocab.word_at(i) for i in idx]
