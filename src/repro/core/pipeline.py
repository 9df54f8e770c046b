"""LIST end-to-end pipeline (paper Algorithm 1): train → index → query.

Public API is the :class:`ListRetriever`:

    retriever = ListRetriever(cfg, corpus)
    retriever.train_relevance(steps=...)     # Eq. 8 contrastive
    retriever.train_index(steps=...)         # Eq. 13 pseudo-labels + Eq. 14 MCL
    retriever.build()                        # indexing phase (cluster buffers)
    ids, scores = retriever.query(q_ids, k)  # query phase (route+score+topk)

The query phase is a single jitted program owned by the unified engine
(core/engine.py): encode → features → route → fused score → top-k.
``backend="pallas"`` runs the GATHER-FREE kernel
(kernels/fused_topk_score_routed): routed cluster ids are
scalar-prefetched and the resident (c, cap, d) buffers block-indexed
directly, so no (B, cr·cap, d) candidate copy is materialized and cr > 1
merges in-kernel. ``backend="dense"`` is the jnp reference path.

The built state is exported as an immutable, versioned
``IndexSnapshot`` (:meth:`ListRetriever.snapshot`, core/snapshot.py) —
the artifact ``repro.api`` saves, loads, and serves.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import engine as engine_lib
from repro.core import index as index_lib
from repro.core import pseudo_labels, relevance
from repro.core import snapshot as snapshot_lib
from repro.core import spatial as sp
from repro.core.baselines import BM25, tkq_topk
from repro.optim import make_optimizer, clip_by_global_norm, linear_warmup_cosine


# ---------------------------------------------------------------------------
# Corpus embedding (offline, batched)
# ---------------------------------------------------------------------------


def embed_objects(params, corpus, cfg, *, batch: int = 512) -> np.ndarray:
    tokens, mask = corpus.object_tokens()
    return _embed(relevance.encode_objects, params, cfg, tokens, mask, batch)


def embed_queries(params, corpus, cfg, query_ids=None, *,
                  batch: int = 512) -> np.ndarray:
    tokens, mask = corpus.query_tokens(query_ids)
    return _embed(relevance.encode_queries, params, cfg, tokens, mask, batch)


def _embed(encode, params, cfg, tokens, mask, batch):
    # params enter as an argument: closed over, they would be baked into
    # the executable as constants (hundreds of MB at BERT-base width)
    jfn = jax.jit(functools.partial(encode, cfg=cfg))
    return engine_lib.run_batched(lambda t, m: jfn(params, t, m),
                                  [tokens, mask], batch=batch)


# ---------------------------------------------------------------------------
# TkQ hard negatives for relevance training (paper §4.2 Training Strategy)
# ---------------------------------------------------------------------------


def mine_tkq_negatives(corpus, query_ids, *, pool: int = 50,
                       alpha: float = 0.4) -> np.ndarray:
    """(len(query_ids), pool) top-TkQ-ranked non-positive objects/query."""
    bm = BM25(corpus.obj_doc, vocab_size=corpus.cfg.vocab_size)
    q_tok = corpus.q_doc[query_ids]
    top = tkq_topk(bm, q_tok, corpus.q_loc[query_ids], corpus.obj_loc,
                   pool * 2, alpha=alpha, dist_max=corpus.dist_max)
    out = np.zeros((len(query_ids), pool), np.int64)
    for i, qi in enumerate(query_ids):
        pos = set(corpus.positives[qi].tolist())
        neg = [o for o in top[i] if o not in pos][:pool]
        while len(neg) < pool:  # top up with randoms
            cand = np.random.default_rng(qi).integers(
                0, corpus.cfg.n_objects, size=pool)
            neg.extend([o for o in cand if o not in pos])
        out[i] = np.array(neg[:pool])
    return out


# ---------------------------------------------------------------------------
# Trainers
# ---------------------------------------------------------------------------


def train_relevance_model(corpus, cfg, *, steps: int = 200, batch: int = 64,
                          lr: float = 3e-4, seed: int = 0,
                          spatial_mode: str = "step",
                          weight_mode: str = "mlp",
                          hard_negatives: bool = True,
                          log_every: int = 50, verbose: bool = False):
    """Contrastive training (Eq. 8). Returns (params, metrics_history)."""
    key = jax.random.PRNGKey(seed)
    params = relevance.relevance_init(key, cfg, spatial_mode=spatial_mode,
                                      weight_mode=weight_mode)
    opt_init, opt_update = make_optimizer(cfg.optimizer)
    opt_state = opt_init(params)
    sched = linear_warmup_cosine(lr, max(steps // 20, 1), steps)
    train_q, _, _ = corpus.split()
    negs = (mine_tkq_negatives(corpus, train_q, pool=16)
            if hard_negatives else None)
    neg_lookup = np.zeros((corpus.cfg.n_queries, 16), np.int64)
    if negs is not None:
        neg_lookup[train_q] = negs

    # params and optimizer state are replaced every step: donating them
    # lets the update reuse their device memory
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(params, opt_state, batch_dev, lr_now):
        def loss_fn(p):
            return relevance.contrastive_loss(
                p, batch_dev, cfg, spatial_mode=spatial_mode,
                weight_mode=weight_mode)
        (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        params, opt_state = opt_update(grads, opt_state, params, lr_now)
        m["grad_norm"] = gnorm
        return params, opt_state, m

    hist = []
    for step in range(steps):
        b = corpus.train_batch(step, batch, train_q,
                               hard_negs=neg_lookup if hard_negatives else None,
                               b_neg=cfg.hard_neg_b)
        b.pop("query_ids")
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt_state, m = step_fn(params, opt_state, b,
                                       sched(jnp.int32(step)))
        if step % log_every == 0 or step == steps - 1:
            rec = {k: float(v) for k, v in m.items()}
            rec["step"] = step
            hist.append(rec)
            if verbose:
                print(f"  [relevance] step {step}: loss={rec['loss']:.4f} "
                      f"acc={rec['acc']:.3f}")
    return params, hist


def train_cluster_index(rel_params, corpus, cfg, *, obj_emb=None,
                        steps: int = 300, batch: int = 64, lr: float = 1e-3,
                        seed: int = 0, neg_start: Optional[int] = None,
                        neg_end: Optional[int] = None, m_negs: Optional[int] = None,
                        log_every: int = 100, verbose: bool = False,
                        spatial_mode="step", weight_mode="mlp"):
    """LIST-I training: Eq. 13 pseudo-negatives + Eq. 14 MCL loss.

    Returns (index_params, loc_norm, obj_emb, history).
    """
    neg_start = cfg.neg_start if neg_start is None else neg_start
    neg_end = cfg.neg_end if neg_end is None else neg_end
    m_negs = cfg.mcl_negatives if m_negs is None else m_negs
    if obj_emb is None:
        obj_emb = embed_objects(rel_params, corpus, cfg)
    obj_loc = corpus.obj_loc.astype(np.float32)
    norm = index_lib.loc_normalizer(jnp.asarray(obj_loc))

    train_q, _, _ = corpus.split()
    q_emb = embed_queries(rel_params, corpus, cfg, train_q)
    q_loc = corpus.q_loc[train_q].astype(np.float32)

    # --- Eq. 13: mine the pseudo-negative window with the relevance model --
    pos_mask = corpus.positives_mask(train_q)
    neg_ids = np.asarray(pseudo_labels.mine_negatives(
        rel_params, cfg, jnp.asarray(q_emb), jnp.asarray(q_loc),
        jnp.asarray(obj_emb), jnp.asarray(obj_loc),
        pos_mask=jnp.asarray(pos_mask), neg_start=neg_start, neg_end=neg_end,
        dist_max=corpus.dist_max, spatial_mode=spatial_mode,
        weight_mode=weight_mode))                       # (Bq, window)

    # --- features ---------------------------------------------------------
    obj_feats = np.asarray(index_lib.build_features(
        jnp.asarray(obj_emb), jnp.asarray(obj_loc), norm))
    q_feats = np.asarray(index_lib.build_features(
        jnp.asarray(q_emb), jnp.asarray(q_loc), norm))

    key = jax.random.PRNGKey(seed + 7)
    iparams = index_lib.index_init(key, obj_emb.shape[1], cfg.n_clusters,
                                   hidden=cfg.index_mlp_hidden)
    opt_init, opt_update = make_optimizer("adamw")
    opt_state = opt_init(iparams)
    sched = linear_warmup_cosine(lr, max(steps // 20, 1), steps)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(iparams, opt_state, fb, lr_now):
        (loss, m), grads = jax.value_and_grad(
            index_lib.mcl_loss, has_aux=True)(iparams, fb)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        iparams, opt_state = opt_update(grads, opt_state, iparams, lr_now)
        m["grad_norm"] = gnorm
        return iparams, opt_state, m

    rng = np.random.default_rng(seed)
    nq = len(train_q)
    hist = []
    for step in range(steps):
        rows = rng.integers(0, nq, size=batch)
        pos_pick = np.array([
            corpus.positives[train_q[r]][
                rng.integers(0, len(corpus.positives[train_q[r]]))]
            for r in rows])
        neg_pick = neg_ids[rows[:, None],
                           rng.integers(0, neg_ids.shape[1],
                                        size=(batch, m_negs))]
        fb = {
            "q_feat": jnp.asarray(q_feats[rows]),
            "pos_feat": jnp.asarray(obj_feats[pos_pick]),
            "neg_feat": jnp.asarray(obj_feats[neg_pick.reshape(-1)]
                                    ).reshape(batch, m_negs, -1),
        }
        iparams, opt_state, m = step_fn(iparams, opt_state, fb,
                                        sched(jnp.int32(step)))
        if step % log_every == 0 or step == steps - 1:
            rec = {k: float(v) for k, v in m.items()}
            rec["step"] = step
            hist.append(rec)
            if verbose:
                print(f"  [index] step {step}: loss={rec['loss']:.4f} "
                      f"s_pos={rec['s_pos']:.3f} s_neg={rec['s_neg']:.3f}")
    return iparams, norm, obj_emb, hist


# ---------------------------------------------------------------------------
# The retriever façade
# ---------------------------------------------------------------------------
# (The jitted query-phase builder lives in core/engine.make_query_fn —
# the former pipeline.make_query_fn wrapper and its use_pallas alias are
# gone; --use-pallas survives only in engine.resolve_cli_backend.)


class ListRetriever:
    """LIST = LIST-R (relevance) + LIST-I (learned cluster index)."""

    def __init__(self, cfg, corpus, *, spatial_mode="step", weight_mode="mlp"):
        self.cfg = cfg
        self.corpus = corpus
        self.spatial_mode = spatial_mode
        self.weight_mode = weight_mode
        self.rel_params = None
        self.index_params = None
        self.norm = None
        self.obj_emb = None
        self.buffers = None
        self.history = {}

    # --- training phase ---------------------------------------------------

    def train_relevance(self, **kw):
        self.rel_params, h = train_relevance_model(
            self.corpus, self.cfg, spatial_mode=self.spatial_mode,
            weight_mode=self.weight_mode, **kw)
        self.history["relevance"] = h
        return h

    def train_index(self, **kw):
        assert self.rel_params is not None, "train_relevance first"
        self.index_params, self.norm, self.obj_emb, h = train_cluster_index(
            self.rel_params, self.corpus, self.cfg, obj_emb=self.obj_emb,
            spatial_mode=self.spatial_mode, weight_mode=self.weight_mode,
            **kw)
        self.history["index"] = h
        return h

    # --- indexing phase -----------------------------------------------------

    def build(self, *, capacity=None, spill: int = 3,
              precision: str = "f32", attrs=None):
        """Indexing phase: pack the corpus into padded cluster buffers,
        optionally quantized (``precision ∈ index.PRECISIONS``,
        DESIGN.md §9 — int8 cuts the query phase's dominant HBM stream
        4×; loc/ids stay exact). ``attrs (n_objects, 3)`` attaches
        per-object filter attributes (core/filters.py, DESIGN.md §13);
        None → all-zero rows."""
        assert self.index_params is not None, "train_index first"
        if self.obj_emb is None:
            self.obj_emb = embed_objects(self.rel_params, self.corpus, self.cfg)
        obj_loc = self.corpus.obj_loc.astype(np.float32)
        feats = index_lib.build_features(
            jnp.asarray(self.obj_emb), jnp.asarray(obj_loc), self.norm)
        top = index_lib.assign_clusters(self.index_params, feats,
                                        top=max(spill, 1))
        if top.ndim == 1:
            top = top[:, None]
        self.buffers = index_lib.build_cluster_buffers(
            np.asarray(top), self.obj_emb, obj_loc,
            n_clusters=self.cfg.n_clusters, capacity=capacity, spill=spill,
            precision=precision, attrs=attrs)
        self.obj_assign = np.asarray(top[:, 0])
        self._engine = None            # buffers changed: invalidate plans
        return self.buffers

    # --- query phase --------------------------------------------------------

    def snapshot(self) -> "snapshot_lib.IndexSnapshot":
        """The immutable, versioned artifact of the current built state
        (core/snapshot.py): what you ``save()``, hand to
        ``repro.api.Searcher``, or publish to a streaming server.

        Re-derived (with ``meta.version`` bumped) whenever the
        retriever's params/buffers objects are swapped — retraining,
        ``index.insert_objects`` / ``delete_objects`` returning new
        buffer dicts — so a fresh call never describes stale state."""
        assert self.buffers is not None, "build() first"
        key = (id(self.rel_params), id(self.index_params), id(self.norm),
               id(self.buffers))
        if (getattr(self, "_snapshot", None) is None
                or getattr(self, "_snapshot_key", None) != key):
            version = getattr(self, "_snapshot_gen", -1) + 1
            self._snapshot_gen = version
            self._snapshot = snapshot_lib.IndexSnapshot.from_parts(
                self.cfg, self.rel_params, self.index_params, self.norm,
                self.buffers, dist_max=float(self.corpus.dist_max),
                spatial_mode=self.spatial_mode,
                weight_mode=self.weight_mode, version=version)
            self._snapshot_key = key
        return self._snapshot

    def engine(self) -> engine_lib.QueryEngine:
        """A stateless engine over :meth:`snapshot` (built lazily after
        build(); rebuilt when the snapshot re-derives, so queries never
        serve a stale index)."""
        snap = self.snapshot()
        if (getattr(self, "_engine", None) is None
                or self._engine.snapshot is not snap):
            self._engine = engine_lib.QueryEngine.from_snapshot(snap)
        return self._engine

    def query(self, query_ids, *, k: int = 20, cr: int = 1,
              backend: Optional[str] = None, batch: int = 256):
        eng = self.engine()
        tokens, mask = self.corpus.query_tokens(query_ids)
        q_loc = self.corpus.q_loc[query_ids].astype(np.float32)
        return eng.query(tokens, mask, q_loc, k=k, cr=cr, batch=batch,
                         backend=backend)

    # --- brute force (LIST-R over the whole corpus) -------------------------

    def brute_force(self, query_ids, *, k: int = 20, batch: int = 256):
        q_emb = embed_queries(self.rel_params, self.corpus, self.cfg,
                              query_ids, batch=batch)
        q_loc = self.corpus.q_loc[query_ids].astype(np.float32)
        obj_loc = self.corpus.obj_loc.astype(np.float32)

        @jax.jit
        def score_top(qe, ql):
            st = relevance.score_corpus(
                self.rel_params, qe, ql, jnp.asarray(self.obj_emb),
                jnp.asarray(obj_loc), self.cfg, dist_max=self.corpus.dist_max,
                spatial_mode=self.spatial_mode, weight_mode=self.weight_mode,
                train=False)
            sc, ids = jax.lax.top_k(st, k)
            return ids, sc

        return engine_lib.run_batched(score_top, [q_emb, q_loc], batch=batch)

    # --- embedding accessor for baselines -----------------------------------

    def ensure_embeddings(self):
        if self.obj_emb is None:
            self.obj_emb = embed_objects(self.rel_params, self.corpus, self.cfg)
        return self.obj_emb

    def score_fn(self):
        """score_fn(query_row_embedding context) for baseline reranking:
        returns fn(q_emb_row, q_loc_row, cand_ids) -> scores.

        Scoring goes through the engine's single ``score_candidates``
        primitive so reranked baselines use the exact serve-path ST."""
        obj_loc = self.corpus.obj_loc.astype(np.float32)
        w_hat = (sp.extract_lookup(self.rel_params["spatial"])
                 if self.spatial_mode == "step"
                 else jnp.linspace(0, 1, self.cfg.spatial_t))

        def fn(q_emb_row, q_loc_row, cand):
            ce = jnp.asarray(self.obj_emb[cand])
            cl = jnp.asarray(obj_loc[cand])
            w = relevance.st_weights(self.rel_params, q_emb_row[None],
                                     weight_mode=self.weight_mode)[0]
            st = engine_lib.score_candidates(
                q_emb_row, q_loc_row, w, ce, cl,
                jnp.asarray(cand, jnp.int32), w_hat,
                dist_max=float(self.corpus.dist_max))
            return np.asarray(st)
        return fn
