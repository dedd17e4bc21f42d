"""LSH-decode: RANGE-LSH over the unembedding matrix (DESIGN.md §4).

Greedy decoding's argmax over logits IS maximum inner product search: the
database is the unembedding matrix (up to 256k rows here — LM vocab rows
have long-tailed 2-norms, exactly the paper's Fig 1b setting) and the query
is the final hidden state. ``VocabIndex`` builds a RANGE-LSH index over the
vocab once per checkpoint; ``lsh_topk_tokens`` ranks vocab rows by the
eq.-12 score from one packed Hamming scan and exactly re-ranks the top-P —
the probes/recall trade-off of the paper's Fig 2 applied to token search.

Compatibility notes:
  * gemma2's final logit softcap is ``cap*tanh(logits/cap)`` — strictly
    monotone, so top-k by inner product == top-k by capped logit; the cap
    is applied after re-ranking.
  * training always uses exact logits (softmax needs the full
    distribution); LSH-decode is serving-only, as the paper's technique is
    query-time (§Arch-applicability).

Distribution: vocab rows are sharded over the ``model`` axis. Norm-range
partitioning is applied *within* each shard (ranges need not cross shards
since eq.-12 scores are globally comparable), each shard re-ranks its local
top-P exactly, and a (vals, ids) all-gather + replicated merge yields the
global top-k — Algorithm 2 as one small collective, same shape as
``core.distributed``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.core.family import SimpleLSHFamily
from repro.core.index import index_bits
from repro.core.partition import effective_upper, percentile_partition
from repro.core.probe import DEFAULT_EPS, item_scores
from repro.kernels import ops
from repro.obs.trace import span_or_null


class VocabIndex(NamedTuple):
    """RANGE-LSH index over the unembedding matrix.

    codes/range_id are in vocab order (NOT norm-sorted): token ids are the
    identity mapping, which keeps the decode path gather-free.
    ``calib`` optionally carries a planner calibration table
    (:func:`calibrate_vocab_index`) so decoding can take a
    ``recall_target`` instead of a hand-picked ``num_probe``.
    """

    codes: jax.Array      # (V, W) uint32
    range_id: jax.Array   # (V,) int32
    upper: jax.Array      # (m,) f32
    A: jax.Array          # (d+1, hash_bits) f32
    code_len: int
    hash_bits: int
    eps: float
    calib: Optional[object] = None


def build_vocab_index(unembed: jax.Array, key: jax.Array, *,
                      code_len: int = 128, num_ranges: int = 64,
                      eps: float = DEFAULT_EPS, impl: str = "auto"
                      ) -> VocabIndex:
    """unembed: (d, V) — indexed over columns (vocab rows)."""
    items = unembed.T.astype(jnp.float32)                 # (V, d)
    norms = hashing.l2_norm(items)
    part = percentile_partition(norms, num_ranges)
    upper = effective_upper(part)
    hash_bits = code_len - index_bits(num_ranges)
    fam = SimpleLSHFamily()
    A = fam.make_params(key, items.shape[-1], hash_bits)
    codes = fam.encode_items(A, items, upper[part.range_id], impl=impl)
    return VocabIndex(codes, part.range_id, part.upper, A, code_len,
                      hash_bits, eps)


def calibrate_vocab_index(index: VocabIndex, unembed: jax.Array,
                          hidden: jax.Array, *, k: int = 10,
                          true_vocab: Optional[int] = None,
                          impl: str = "auto"):
    """Planner calibration for LSH-decode (DESIGN.md §12): measure where
    the exact top-k tokens of held-out hidden states land in the head's
    dense probe order, and return the fitted table — attach it with
    ``index._replace(calib=...)`` so ``lsh_topk_tokens`` can honor a
    ``recall_target``. ``hidden`` should be real decode-time hidden
    states (the serving distribution), ``(B, d)``."""
    from repro.core.planner import calibrate_from_order

    q = hashing.normalize(hidden.astype(jnp.float32))
    zeros = jnp.zeros((q.shape[0],), q.dtype)
    q_codes = ops.hash_encode(q, index.A[:-1], zeros, index.A[-1],
                              impl=impl)
    ham = ops.hamming_scan(q_codes, index.codes, impl=impl)
    scores = item_scores(index.upper, index.range_id, ham,
                         index.hash_bits, index.eps)
    if true_vocab is not None and true_vocab < index.codes.shape[0]:
        scores = jnp.where(jnp.arange(index.codes.shape[0]) < true_vocab,
                           scores, -jnp.inf)
    # ties break by lower id, matching lax.top_k in the probe path
    order = np.argsort(-np.asarray(jax.device_get(scores)), axis=1,
                       kind="stable")
    _, truth = exact_topk_tokens(hidden, unembed, k,
                                 true_vocab=true_vocab)
    return calibrate_from_order(
        order, np.asarray(jax.device_get(index.range_id)),
        np.asarray(jax.device_get(truth)),
        num_ranges=int(index.upper.shape[0]))


DEFAULT_NUM_PROBE = 1024


def lsh_topk_tokens(index: VocabIndex, hidden: jax.Array,
                    unembed: jax.Array, *, k: int = 8,
                    num_probe: Optional[int] = None,
                    final_softcap: Optional[float] = None,
                    true_vocab: Optional[int] = None,
                    impl: str = "auto",
                    buckets=None,
                    recall_target: Optional[float] = None,
                    tracker=None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Approximate top-k tokens for hidden states (B, d).

    Returns (logit_vals (B, k) f32, token_ids (B, k) int32). Probes the
    ``num_probe`` best vocab rows by the eq.-12 score, then re-ranks them
    with exact inner products against the unembedding. ``true_vocab``
    excludes vocab-padding rows (configs/base.py padded_vocab).

    ``buckets`` (a :class:`repro.core.bucket_index.BucketIndex` built over
    the vocab codes) switches candidate generation to the bucket engine —
    O(B log B) directory work instead of the dense (B, V) scan +
    top_k. Padding rows may then consume probe budget (they are still
    excluded from the final top-k by the ``true_vocab`` re-rank mask).

    ``recall_target`` plans ``num_probe`` from the planner's
    global-prefix budget in the index's calibration table — the decode
    head's recall contract (the scan is one global probe order, so the
    scalar curve applies; see ``calibrate_vocab_index``). Exactly one of
    the two may be passed; with neither, ``DEFAULT_NUM_PROBE`` applies.

    ``tracker`` (a :class:`repro.obs.Tracker`) times the candidate scan
    and re-rank stages — EAGER callers only: this function is also traced
    inside jitted decode steps, where the default ``None`` keeps the
    spans as compile-time no-ops.
    """
    if recall_target is not None:
        from repro.core.planner import check_contract_k, plan_global
        if num_probe is not None:
            raise ValueError("pass one of num_probe/recall_target")
        if index.calib is not None:
            check_contract_k(index.calib, k)
        if index.calib is None:
            raise ValueError(
                "recall_target needs a calibrated VocabIndex — attach "
                "calibrate_vocab_index() via index._replace(calib=...)")
        if buckets is not None and true_vocab is not None \
                and true_vocab < index.codes.shape[0]:
            # the bucket walk spends budget on padding rows the dense
            # calibration masked out, silently under-delivering recall
            raise ValueError(
                "recall_target with engine='bucket' needs a padding-free "
                "store: build the index/buckets over the true vocab rows "
                "(as build_sharded_vocab_index does) instead of masking "
                "with true_vocab")
        num_probe = plan_global(index.calib, recall_target).num_probe
    elif num_probe is None:
        num_probe = DEFAULT_NUM_PROBE
    with span_or_null(tracker, "repro.models.lm_head.candidates") as sp:
        q = hashing.normalize(hidden.astype(jnp.float32))
        zeros = jnp.zeros((q.shape[0],), q.dtype)
        q_codes = ops.hash_encode(q, index.A[:-1], zeros, index.A[-1],
                                  impl=impl)
        if buckets is not None:
            from repro.core.engine import bucket_candidates
            cand = bucket_candidates(buckets, q_codes, num_probe, impl=impl)
        else:
            ham = ops.hamming_scan(q_codes, index.codes, impl=impl)  # (B, V)
            scores = item_scores(index.upper, index.range_id, ham,
                                 index.hash_bits, index.eps)
            if true_vocab is not None and true_vocab < index.codes.shape[0]:
                scores = jnp.where(
                    jnp.arange(index.codes.shape[0]) < true_vocab,
                    scores, -jnp.inf)
            _, cand = jax.lax.top_k(scores, num_probe)               # (B, P)
        cand = sp.sync(cand)
    with span_or_null(tracker, "repro.models.lm_head.re_rank") as sp:
        cand_vecs = jnp.take(unembed, cand, axis=1)           # (d,) gather
        # unembed is (d, V): gather columns -> (d, B, P); contract d
        logits = jnp.einsum("bd,dbp->bp", hidden.astype(jnp.float32),
                            cand_vecs.astype(jnp.float32))
        if true_vocab is not None:
            logits = jnp.where(cand < true_vocab, logits, -jnp.inf)
        vals, pos = jax.lax.top_k(logits, k)
        ids = sp.sync(jnp.take_along_axis(cand, pos, axis=1))
    if tracker is not None:
        tracker.count("repro.models.lm_head.queries", hidden.shape[0])
        tracker.observe("repro.models.lm_head.num_probe", num_probe)
    if final_softcap is not None:   # monotone: order unchanged
        vals = final_softcap * jnp.tanh(vals / final_softcap)
    return vals, ids


def exact_topk_tokens(hidden: jax.Array, unembed: jax.Array, k: int,
                      final_softcap: Optional[float] = None,
                      true_vocab: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Exact baseline: full (B, V) logits + top_k."""
    logits = jnp.einsum("bd,dv->bv", hidden.astype(jnp.float32),
                        unembed.astype(jnp.float32))
    if final_softcap is not None:
        logits = final_softcap * jnp.tanh(logits / final_softcap)
    if true_vocab is not None and true_vocab < unembed.shape[1]:
        logits = jnp.where(jnp.arange(unembed.shape[1]) < true_vocab,
                           logits, -jnp.inf)
    return jax.lax.top_k(logits, k)


def sharded_lsh_topk_tokens(index: VocabIndex, hidden: jax.Array,
                            unembed: jax.Array, mesh, *, k: int = 8,
                            num_probe_per_shard: int = 256,
                            axis: str = "model", impl: str = "auto"
                            ) -> Tuple[jax.Array, jax.Array]:
    """Vocab-sharded LSH-decode (Algorithm 2 as one all-gather).

    index arrays and ``unembed`` must be sharded over ``axis`` on the vocab
    dimension; ``hidden`` replicated across it. ``impl`` dispatches the
    encode/scan kernels ("auto" = Pallas on TPU). Returns replicated
    (vals, ids) with *global* token ids.
    """
    from jax.sharding import PartitionSpec as P

    V = unembed.shape[1]
    shards = mesh.shape[axis]
    v_loc = V // shards

    def local(codes, rid, upper, A, hid, unemb):
        q = hashing.normalize(hid.astype(jnp.float32))
        zeros = jnp.zeros((q.shape[0],), q.dtype)
        qc = ops.hash_encode(q, A[:-1], zeros, A[-1], impl=impl)
        ham = ops.hamming_scan(qc, codes, impl=impl)
        sc = item_scores(upper, rid, ham, index.hash_bits, index.eps)
        _, cand = jax.lax.top_k(sc, num_probe_per_shard)      # local ids
        cv = jnp.take(unemb, cand, axis=1)                    # (d, B, P)
        logits = jnp.einsum("bd,dbp->bp", hid.astype(jnp.float32),
                            cv.astype(jnp.float32))
        vals, pos = jax.lax.top_k(logits, k)
        ids = jnp.take_along_axis(cand, pos, axis=1)
        ids = ids + jax.lax.axis_index(axis) * v_loc          # global ids
        av = jax.lax.all_gather(vals, axis)                   # (S, B, k)
        ai = jax.lax.all_gather(ids, axis)
        S, B, K = av.shape
        fv = jnp.transpose(av, (1, 0, 2)).reshape(B, S * K)
        fi = jnp.transpose(ai, (1, 0, 2)).reshape(B, S * K)
        bv, bp = jax.lax.top_k(fv, k)
        return bv, jnp.take_along_axis(fi, bp, axis=1)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(), P(None, None), P(),
                  P(None, axis)),
        out_specs=(P(), P()),
        check_vma=False)
    return fn(index.codes, index.range_id, index.upper, index.A, hidden,
              unembed)
