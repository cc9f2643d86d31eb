"""SequenceVectors: the generic embedding trainer.

Parity with the reference's framework (reference:
deeplearning4j-nlp/.../models/sequencevectors/SequenceVectors.java:51,
fit():187): build vocab → reset lookup weights → train elements/sequence
learning algorithm over the corpus. The reference spawns
VectorCalculationsThreads racing hogwild updates (:289); here the corpus
is turned into fixed-shape index batches on the host and each batch is
one jitted XLA step (learning.py) — the TPU-idiomatic equivalent
(SURVEY.md §3.4).
"""
from __future__ import annotations

import logging
from typing import Iterable, List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from deeplearning4j_tpu.nlp import learning
from deeplearning4j_tpu.nlp.lookup import InMemoryLookupTable
from deeplearning4j_tpu.nlp.vocab import AbstractCache, VocabConstructor
from deeplearning4j_tpu.nlp.word_vectors import WordVectorsMixin

log = logging.getLogger(__name__)

# max batches per scanned program — bounds staging memory for all the
# embedding scan paths (skip-gram, ParagraphVectors, GloVe)
SCAN_CHUNK = 1024


def iter_scan_chunks(batch_size: int, chunk: int, n_batches: int,
                     n_items: int):
    """Yield (sl, nb, nb_pad, n_valid) per chunk of up to ``chunk``
    batches. nb_pad buckets partial chunks to the next power of two so
    per-epoch item-count jitter never recompiles the scan program.
    Shared by the skip-gram, ParagraphVectors, and GloVe scan paths."""
    for start in range(0, n_batches, chunk):
        nb = min(chunk, n_batches - start)
        nb_pad = nb if nb == chunk else max(16, 1 << (nb - 1).bit_length())
        lo = start * batch_size
        n_valid = min(n_items - lo, nb * batch_size)
        yield slice(lo, lo + nb * batch_size), nb, nb_pad, n_valid


def stage_chunk(a: np.ndarray, sl: slice, nb_pad: int, n_valid: int,
                batch_size: int, fill=0) -> np.ndarray:
    """Pad a chunk's rows with ``fill`` and reshape to [nb_pad, B, ...]."""
    flat = np.concatenate(
        [a[sl], np.full((nb_pad * batch_size - n_valid,) + a.shape[1:],
                        fill, a.dtype)])
    return flat.reshape((nb_pad, batch_size) + a.shape[1:])


class SequenceVectors(WordVectorsMixin):
    """Generic trainer over sequences of elements (words, graph-walk
    vertices, document labels...). Subclasses (Word2Vec, ParagraphVectors,
    DeepWalk's GraphVectors) mostly just configure the pipeline — same
    shape as the reference hierarchy."""

    def __init__(self, *, layer_size: int = 100, window: int = 5,
                 learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4,
                 negative: int = 5, use_hierarchic_softmax: bool = False,
                 epochs: int = 1, iterations: int = 1,
                 min_word_frequency: int = 1, batch_size: int = 512,
                 subsampling: float = 0.0, seed: int = 12345,
                 elements_learning_algorithm: str = "skipgram",
                 mesh=None, scan_epochs: bool = True):
        self.layer_size = layer_size
        self.window = window
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.epochs = epochs
        self.iterations = iterations
        self.min_word_frequency = min_word_frequency
        self.batch_size = batch_size
        self.subsampling = subsampling
        # scanned whole-epoch programs (skip-gram/neg); False forces the
        # per-batch dispatch path (they are numerically identical — the
        # equivalence test in tests/test_nlp.py is the proof obligation)
        self.scan_epochs = scan_epochs
        self.seed = seed
        self.algorithm = elements_learning_algorithm.lower()
        # device mesh with a 'data' axis → mesh-sharded pair batches (the
        # distributed Word2Vec mode; see make_sharded_skipgram_step)
        self.mesh = mesh
        # unsupported mesh combinations fail before any construction work
        if mesh is not None and self.algorithm != "skipgram":
            raise ValueError("mesh-distributed training currently covers "
                             "the skipgram algorithm")
        if mesh is not None and self.use_hs:
            raise ValueError("mesh-distributed training currently covers "
                             "skipgram with negative sampling, not "
                             "hierarchical softmax")
        # sharded step/scan built eagerly (jit wrapping is lazy; nothing
        # compiles until first call); _sharded_fns() rebuilds on demand
        # if a mesh is assigned after construction
        if mesh is not None:
            self._sharded_step = learning.make_sharded_skipgram_step(mesh)
            self._sharded_scan = learning.make_sharded_skipgram_scan(mesh)
        else:
            self._sharded_step = None
            self._sharded_scan = None
        self.vocab: Optional[AbstractCache] = None
        self.lookup_table: Optional[InMemoryLookupTable] = None
        self._rng = np.random.default_rng(seed)

    def _sharded_fns(self):
        """(step, scan) for the current mesh — rebuilt on demand when a
        mesh was assigned after construction."""
        if self._sharded_step is None:
            self._sharded_step = learning.make_sharded_skipgram_step(
                self.mesh)
            self._sharded_scan = learning.make_sharded_skipgram_scan(
                self.mesh)
        return self._sharded_step, self._sharded_scan

    # -- corpus access (subclasses override) -------------------------------
    def _sequences(self) -> Iterable[List[str]]:
        raise NotImplementedError

    # -- vocab -------------------------------------------------------------
    def _tokenized_corpus(self) -> List[List[str]]:
        """Tokenize the corpus ONCE per model and cache the token lists.

        Profiled r5 (v=100k, 2M tokens): the corpus was tokenized TWICE
        — once for vocab counting, once for encoding — at ~3s per pass
        through the per-token tokenizer protocol; this cache plus the
        tokenizer fast path removes the second pass entirely. Memory:
        the token lists hold references to the tokenizer's strings
        (~50 bytes/token), the same order of magnitude as the corpora
        the reference's CollectionSentenceIterator already holds in
        RAM; file-based iterators trade that RAM for the staging speed
        the same way the encoded-corpus cache (r3) already does."""
        if getattr(self, "_tokens_cache", None) is None:
            fast = self._default_tokenize_fast()
            self._tokens_cache = (fast if fast is not None
                                  else list(self._sequences()))
        return self._tokens_cache

    def _default_tokenize_fast(self):
        """When the model uses a plain DefaultTokenizerFactory with no
        preprocessor, tokenize without the per-sentence Tokenizer
        object protocol (profiled r5: ~0.4s/2M tokens of pure object
        overhead). Returns None when the configured factory is
        anything else — the protocol path stays authoritative."""
        fac = getattr(self, "tokenizer_factory", None)
        it = getattr(self, "sentence_iterator", None)
        from deeplearning4j_tpu.nlp.tokenization import \
            DefaultTokenizerFactory
        if (it is None or type(fac) is not DefaultTokenizerFactory
                or fac._pre is not None):
            return None
        split = DefaultTokenizerFactory._SPLIT.split
        it.reset()
        out = []
        for sentence in it:
            toks = [t for t in split(sentence.strip()) if t]
            if toks:
                out.append(toks)
        return out

    def build_vocab(self) -> None:
        """Reference: SequenceVectors.buildVocabIfNecessary →
        VocabConstructor.buildJointVocabulary (VocabConstructor.java:168)."""
        constructor = VocabConstructor(
            min_word_frequency=self.min_word_frequency,
            build_huffman=self.use_hs)
        # a vocab (re)build must see the CURRENT corpus: drop any token
        # cache from a previous build before re-reading the iterator
        # (the fresh cache is then shared with _encoded_corpus below)
        self._tokens_cache = None
        self.vocab = constructor.build_vocab(self._tokenized_corpus())
        self._finish_vocab_build()

    def _finish_vocab_build(self) -> None:
        """Build the lookup table and drop every vocab-derived staging
        cache — the ONE invalidation point shared with subclass
        build_vocab overrides (scaleout.DistributedSequenceVectors)."""
        self.lookup_table = InMemoryLookupTable(
            self.vocab, self.layer_size, seed=self.seed,
            use_hs=self.use_hs, use_neg=self.negative > 0)
        self.lookup_table.reset_weights()
        # vocab changed: encoded-corpus, frequency and pooled-negative
        # caches are stale (the pool indexes the OLD unigram table)
        self._corpus_cache = None
        self._freq_cache = None
        self._neg_pool = None
        self._neg_cursor = 0
        self._pv_staging = None   # ParagraphVectors' staged windows
        self._hs_tables_dev = None  # device-resident Huffman tables

    # -- training pair generation (host-side, IO/string bound) ------------
    def _encode(self, seq: Sequence[str]) -> np.ndarray:
        idx = [self.vocab.index_of(w) for w in seq]
        return np.array([i for i in idx if i >= 0], dtype=np.int32)

    def _reduced_windows(self, n: int):
        """The word2vec reduced-window draw: per-position effective
        window sizes w [n] (>=1) and the symmetric offset vector
        [-window..-1, 1..window]. One definition keeps the pair and
        CBOW-row staging on the same RNG stream structurally."""
        w = self.window - self._rng.integers(0, self.window, n)
        offs = np.concatenate([np.arange(-self.window, 0),
                               np.arange(1, self.window + 1)])
        return w, offs

    # -- whole-corpus staging (round-3: the profiled epoch bottleneck was
    # host work — re-tokenizing, per-token vocab attribute chases, and
    # 60k-call-per-epoch pair generation; one pass of numpy over the
    # cached encoded corpus replaces all of it) -------------------------
    def _encoded_corpus(self):
        """Encode the cached token corpus ONCE per vocab (the reference
        re-tokenizes every epoch, SequenceVectors.java; epochs after the
        first reuse the flat int corpus). Returns (flat ids [N] int32,
        per-sentence KEPT-token lengths [S]).

        One flat pass with a plain word->index dict + vectorized
        unknown-word filtering (r5: the per-sentence _encode loop — 2M
        index_of method calls + 100k small array builds — was ~3.2s of
        the v=100k staging profile; this is ~0.6s)."""
        if getattr(self, "_corpus_cache", None) is None:
            # subclasses may yield EMPTY token lists (e.g. blank
            # sentences through scaleout's unfiltered tokenizer);
            # drop them here — zero-length sentences contribute no
            # tokens and no pairs, and np.add.reduceat below needs
            # strictly increasing starts (r5 review)
            toks = [t for t in self._tokenized_corpus() if t]
            d = {w: i for i, w in enumerate(self.vocab.words())}
            get = d.get
            ids = np.array([get(t, -1) for s in toks for t in s],
                           np.int32)
            lens_all = np.fromiter((len(s) for s in toks), np.int64,
                                   count=len(toks))
            if ids.size:
                valid = ids >= 0
                flat = ids[valid]
                starts = np.concatenate(
                    [[0], np.cumsum(lens_all)[:-1]])
                lens = np.add.reduceat(
                    valid.astype(np.int64), starts)
                # reduceat quirk: a zero-length sentence would alias
                # the next sentence's first element; the empty-list
                # filter above is what guarantees strictly increasing
                # starts — scaleout subclasses DO yield empty token
                # lists for blank sentences, so the filter is
                # load-bearing, not defensive.
            else:
                flat = np.empty(0, np.int32)
                lens = np.zeros(len(toks), np.int64)
            self._corpus_cache = (flat, lens)
        return self._corpus_cache

    def _freq_arr(self) -> np.ndarray:
        """Per-index corpus frequencies as one array (vectorized
        subsampling; cached alongside the corpus)."""
        if getattr(self, "_freq_cache", None) is None:
            nw = self.vocab.num_words()
            self._freq_cache = np.array(
                [self.vocab.word_at_index(i).element_frequency
                 for i in range(nw)], np.float64)
        return self._freq_cache

    def _subsampled_corpus(self):
        """One epoch's subsampled view of the cached corpus: flat kept
        ids + their sentence ids (same keep probabilities as the
        reference's per-sentence subsampling, drawn corpus-wide)."""
        flat, lens = self._encoded_corpus()
        sid = np.repeat(np.arange(len(lens)), lens)
        if self.subsampling > 0 and len(flat):
            freqs = self._freq_arr()[flat] / self.vocab.total_word_count
            keep_p = np.minimum(1.0, np.sqrt(self.subsampling / freqs)
                                + self.subsampling / freqs)
            keep = self._rng.random(len(flat)) < keep_p
            flat, sid = flat[keep], sid[keep]
        return flat, sid

    # centers per staging chunk: bounds the O(chunk * 2*window) index
    # intermediates (the all-at-once form built five corpus x 2w arrays
    # — multi-GB at 10M+ tokens)
    _STAGE_CHUNK = 1 << 20

    def _corpus_window_pairs(self):
        """All (center, context) pairs for one epoch; sentence
        boundaries respected via sentence ids, token-major pair order
        (same as the reference's per-sentence loop). The expansion runs
        in C++ when the native IO library is available
        (native_bridge.window_pairs — r5: this was the largest
        per-epoch host staging cost at v=100k) with the vectorized
        numpy fallback below; the reduced-window RNG draw happens HERE
        either way, so both paths are bit-identical."""
        flat, sid = self._subsampled_corpus()
        n = len(flat)
        if n == 0:
            return (np.empty(0, np.int32),) * 2
        w, offs = self._reduced_windows(n)
        from deeplearning4j_tpu import native_bridge
        if getattr(self, "_pair_bufs", None) is None:
            self._pair_bufs = [np.empty(0, np.int32),
                               np.empty(0, np.int32)]
        native = native_bridge.window_pairs(flat, sid, w, self.window,
                                            bufs=self._pair_bufs)
        if native is not None:
            return native
        k = len(offs)
        cs, xs = [], []
        for lo in range(0, n, self._STAGE_CHUNK):
            hi = min(lo + self._STAGE_CHUNK, n)
            # int32 indices: half the bandwidth of the default int64 on
            # the hottest staging arrays (corpora stay < 2^31 tokens)
            ci = np.repeat(np.arange(lo, hi, dtype=np.int32), k)
            off_t = np.tile(offs.astype(np.int32), hi - lo)
            xi = ci + off_t
            valid = ((xi >= 0) & (xi < n)
                     & (np.abs(off_t) <= np.repeat(w[lo:hi], k)))
            xi_c = np.clip(xi, 0, n - 1)
            valid &= sid[xi_c] == sid[ci]
            cs.append(flat[ci[valid]])
            xs.append(flat[xi[valid]])
        return (np.concatenate(cs).astype(np.int32, copy=False),
                np.concatenate(xs).astype(np.int32, copy=False))

    def _corpus_window_rows(self):
        """All CBOW training rows for one epoch (targets [n], windows
        [n, 2w], mask [n, 2w]) — chunked like _corpus_window_pairs."""
        flat, sid = self._subsampled_corpus()
        n = len(flat)
        if n == 0:
            z = np.empty((0, 2 * self.window))
            return (np.empty(0, np.int32), z.astype(np.int32),
                    z.astype(np.float32))
        w, offs = self._reduced_windows(n)
        wins, masks = [], []
        for lo in range(0, n, self._STAGE_CHUNK):
            hi = min(lo + self._STAGE_CHUNK, n)
            idx = np.arange(lo, hi, dtype=np.int64)[:, None] + offs[None]
            inb = (idx >= 0) & (idx < n)
            cidx = np.clip(idx, 0, n - 1)
            valid = (inb & (sid[cidx] == sid[lo:hi, None])
                     & (np.abs(offs)[None, :] <= w[lo:hi, None]))
            wins.append(np.where(valid, flat[cidx], 0))
            masks.append(valid)
        return (flat.astype(np.int32, copy=False),
                np.concatenate(wins).astype(np.int32, copy=False),
                np.concatenate(masks).astype(np.float32))

    # -- fit ---------------------------------------------------------------
    def fit(self) -> "SequenceVectors":
        """Reference: SequenceVectors.fit():187."""
        if self.vocab is None:
            self.build_vocab()
        total_epochs = self.epochs * self.iterations
        step_no = 0
        # pre-collect pairs per epoch (host); batches keep a fixed shape
        for epoch in range(total_epochs):
            if self.algorithm == "cbow":
                step_no = self._fit_cbow_epoch(step_no, total_epochs,
                                               epoch)
                continue
            centers_a, contexts_a = self._corpus_window_pairs()
            n_pairs = len(centers_a)
            if n_pairs == 0:
                continue
            # epoch shuffle: native paired Fisher-Yates (seeded from
            # this model's numpy Generator — ONE draw, so runs stay
            # reproducible) with a packed-int64 numpy fallback. r5:
            # permutation + two 10M-element gathers was a profiled
            # per-epoch staging cost; the numpy Generator's own
            # shuffle holds the GIL for ~0.7s at 10M pairs.
            from deeplearning4j_tpu import native_bridge
            seed = int(self._rng.integers(0, 2 ** 63))
            centers_a = np.ascontiguousarray(centers_a, np.int32)
            contexts_a = np.ascontiguousarray(contexts_a, np.int32)
            if not native_bridge.pair_shuffle(centers_a, contexts_a,
                                              seed):
                packed = ((centers_a.astype(np.int64) << 32)
                          | contexts_a.astype(np.int64))
                self._rng.shuffle(packed)
                centers_a = (packed >> 32).astype(np.int32)
                contexts_a = (packed & 0xFFFFFFFF).astype(np.int32)
            alpha0 = self.learning_rate
            n_batches = (n_pairs + self.batch_size - 1) // self.batch_size
            total_steps = total_epochs * n_batches
            # scanned when there's something to train (hs or neg) and
            # the mode has a scan kernel (mesh covers neg only)
            scannable = (self.scan_epochs and self.algorithm == "skipgram"
                         and (self.use_hs or self.negative > 0)
                         and (self.mesh is None or not self.use_hs))
            if scannable:
                # whole-epoch scanned program (one dispatch per epoch)
                step_no = self._fit_epoch_scanned(
                    centers_a, contexts_a, n_batches, step_no,
                    total_steps, alpha0)
            else:
                for s in range(0, n_pairs, self.batch_size):
                    lr_now = self._lr_at(step_no, total_steps, alpha0)
                    self._train_batch(
                        centers_a[s:s + self.batch_size],
                        contexts_a[s:s + self.batch_size], lr_now)
                    step_no += 1
            log.info("SequenceVectors epoch %d: %d pairs", epoch, n_pairs)
        return self

    def _lr_at(self, step: int, total_steps: int, alpha0: float) -> float:
        """The word2vec linear lr decay with the min-lr floor — the one
        scalar definition; _chunk_lr vectorizes it for scanned chunks."""
        frac = min(1.0, step / max(total_steps, 1))
        return max(self.min_learning_rate, alpha0 * (1.0 - frac))

    def _fit_cbow_epoch(self, step_no: int, total_epochs: int,
                        epoch: int) -> int:
        """One CBOW epoch (reference CBOW.java): the mean over the
        reduced window predicts the center, through negative sampling
        or — when use_hs — the center's Huffman path (HS takes
        precedence, as in the skip-gram dispatch). Scanned chunks when
        eligible, per-batch dispatch otherwise — both bit-identical
        (the equivalence test's obligation)."""
        if self.negative <= 0 and not self.use_hs:
            raise ValueError("cbow requires negative sampling "
                             "(negative > 0) or hierarchical softmax")
        tgt, win, msk = self._corpus_window_rows()
        n_ex = len(tgt)
        if n_ex == 0:
            return step_no
        order = self._rng.permutation(n_ex)
        tgt, win, msk = tgt[order], win[order], msk[order]
        b = self.batch_size
        n_batches = (n_ex + b - 1) // b
        total_steps = total_epochs * n_batches
        alpha0 = self.learning_rate
        lt = self.lookup_table
        if self.use_hs:
            pts_t = np.asarray(lt.points)
            codes_t = np.asarray(lt.codes)
            cmask_t = np.asarray(lt.code_mask)

        if self.scan_epochs and self.mesh is None:
            for sl, nb, nb_pad, n_valid in self._iter_scan_chunks(
                    n_batches, n_ex):
                windows = self._stage_chunk(win, sl, nb_pad, n_valid)
                wmask = self._stage_chunk(msk, sl, nb_pad, n_valid)
                targets = self._stage_chunk(tgt, sl, nb_pad, n_valid)
                lr_vec = self._chunk_lr(step_no, nb_pad, total_steps,
                                        alpha0, n_valid)
                if self.use_hs:
                    lt.syn0, lt.syn1, _ = learning.cbow_hs_scan(
                        lt.syn0, lt.syn1, jnp.asarray(windows),
                        jnp.asarray(wmask), jnp.asarray(pts_t[targets]),
                        jnp.asarray(codes_t[targets]),
                        jnp.asarray(cmask_t[targets]),
                        jnp.asarray(lr_vec))
                else:
                    negs = self._stage_negatives(nb, nb_pad)
                    lt.syn0, lt.syn1neg, _ = learning.cbow_neg_scan(
                        lt.syn0, lt.syn1neg, jnp.asarray(windows),
                        jnp.asarray(wmask), jnp.asarray(targets),
                        jnp.asarray(negs), jnp.asarray(lr_vec))
                step_no += nb
        else:
            for s in range(0, n_ex, b):
                nb = len(tgt[s:s + b])
                lr_vec = np.zeros(b, np.float32)
                lr_vec[:nb] = self._lr_at(step_no, total_steps, alpha0)
                win_b = jnp.asarray(self._pad(win[s:s + b]))
                msk_b = jnp.asarray(self._pad(msk[s:s + b]))
                tgt_b = self._pad(tgt[s:s + b])
                if self.use_hs:
                    lt.syn0, lt.syn1, _ = learning.cbow_hs_step(
                        lt.syn0, lt.syn1, win_b, msk_b,
                        jnp.asarray(pts_t[tgt_b]),
                        jnp.asarray(codes_t[tgt_b]),
                        jnp.asarray(cmask_t[tgt_b]),
                        jnp.asarray(lr_vec))
                else:
                    lt.syn0, lt.syn1neg, _ = learning.cbow_neg_step(
                        lt.syn0, lt.syn1neg, win_b, msk_b,
                        jnp.asarray(tgt_b),
                        jnp.asarray(self._sample_negatives()),
                        jnp.asarray(lr_vec))
                step_no += 1
        log.info("SequenceVectors cbow epoch %d: %d examples", epoch,
                 n_ex)
        return step_no


    # max batches per scanned program: bounds device/host staging memory
    # at CHUNK * batch_size * (2 + negative) int32 regardless of corpus
    # size (the per-batch path's O(batch) memory, amortized dispatch)
    _SCAN_CHUNK = SCAN_CHUNK

    def _iter_scan_chunks(self, n_batches: int, n_items: int):
        return iter_scan_chunks(self.batch_size, self._SCAN_CHUNK,
                                n_batches, n_items)

    def _stage_chunk(self, a: np.ndarray, sl: slice, nb_pad: int,
                     n_valid: int) -> np.ndarray:
        return stage_chunk(a, sl, nb_pad, n_valid, self.batch_size)

    def _chunk_lr(self, step_no: int, nb_pad: int, total_steps: int,
                  alpha0: float, n_valid: int) -> np.ndarray:
        """Per-row lr schedule for one scanned chunk [nb_pad, B]: linear
        decay by global step with the min-lr floor, zeros on padding
        rows — the ONE definition both the skip-gram and CBOW scanned
        paths share with the per-batch schedule."""
        frac = np.minimum(1.0, (step_no + np.arange(nb_pad))
                          / max(total_steps, 1))
        lr_rows = np.maximum(self.min_learning_rate,
                             alpha0 * (1.0 - frac)).astype(np.float32)
        lr_vec = np.repeat(lr_rows[:, None], self.batch_size, axis=1)
        lr_vec.reshape(-1)[n_valid:] = 0.0
        return lr_vec

    def _stage_negatives(self, nb: int, nb_pad: int) -> np.ndarray:
        """Negatives for one scanned chunk, zero-padded to the bucketed
        chunk size. Consumes the same pooled stream as the per-batch
        path (_sample_negatives) — in whole SLABS of consecutive pool
        rows rather than a per-batch Python loop (r5: the
        stack-of-1024-arrays build was a profiled staging cost), so the
        scanned/stepped equivalence still holds by construction: the
        pool refill points and row order are identical."""
        slabs = []
        need = nb
        while need > 0:
            pool = getattr(self, "_neg_pool", None)
            if pool is None or self._neg_cursor >= len(pool):
                self._refill_neg_pool()
                pool = self._neg_pool
            take = min(need, len(pool) - self._neg_cursor)
            slabs.append(pool[self._neg_cursor:self._neg_cursor + take])
            self._neg_cursor += take
            need -= take
        if len(slabs) == 1 and nb_pad == nb:
            return slabs[0]            # aligned chunk: zero-copy view
        # assemble into a cached buffer (fresh concat allocations were
        # a profiled cost; jnp.asarray copies to device before the
        # next chunk can overwrite this buffer)
        shape = (nb_pad, self.batch_size, self.negative)
        out = getattr(self, "_neg_out_buf", None)
        if out is None or out.shape != shape:
            out = np.empty(shape, np.int32)
            if nb_pad == self._SCAN_CHUNK:
                self._neg_out_buf = out
        pos = 0
        for s in slabs:
            out[pos:pos + len(s)] = s
            pos += len(s)
        if nb_pad > nb:
            out[nb:] = 0
        return out

    def _fit_epoch_scanned(self, centers_a: np.ndarray,
                           contexts_a: np.ndarray, n_batches: int,
                           step_no: int, total_steps: int,
                           alpha0: float) -> int:
        """Run one skip-gram epoch (negative-sampling OR hierarchical
        softmax; CBOW lives in _fit_cbow_epoch) as a few big XLA
        programs: the pair stream is staged in chunks of up to
        _SCAN_CHUNK batches [N, B] and each chunk scans the batched
        update on device (learning.*_scan).
        Padding rows carry lr=0, so they are exact no-ops; partial
        chunks bucket N to the next power of two so epoch-to-epoch
        pair-count jitter (the reduced-window RNG) never recompiles.
        RNG draws happen one batch at a time in stream order, so results
        are bit-identical to the per-batch path."""
        b = self.batch_size
        lt = self.lookup_table
        if self.use_hs:
            # Huffman tables DEVICE-RESIDENT for the whole fit (r5):
            # the r4 path gathered [chunk, B, L] points/codes/mask on
            # the host and staged ~3 full panels per chunk to the
            # device — the profiled reason HS ran 9x under neg
            # sampling. [V, L] is ~20MB at v=100k; upload once, gather
            # by context id inside the kernel.
            if getattr(self, "_hs_tables_dev", None) is None:
                # PRIVATE COPIES: the scan donates its table carries,
                # and jnp.asarray on the lookup table's own jax arrays
                # would be a no-op alias — donation would delete
                # lt.points/codes/code_mask out from under the stepped
                # and CBOW HS paths (r5 review)
                self._hs_tables_dev = (
                    jnp.array(lt.points, copy=True),
                    jnp.array(lt.codes, copy=True),
                    jnp.array(lt.code_mask, copy=True))
            pts_d, codes_d, cmask_d = self._hs_tables_dev
        for sl, nb, nb_pad, n_valid in self._iter_scan_chunks(
                n_batches, len(centers_a)):
            centers_p = self._stage_chunk(centers_a, sl, nb_pad, n_valid)
            contexts_p = self._stage_chunk(contexts_a, sl, nb_pad, n_valid)
            lr_vec = self._chunk_lr(step_no, nb_pad, total_steps,
                                    alpha0, n_valid)
            if self.use_hs:
                # hierarchical softmax: the CONTEXT word's Huffman
                # path/codes, the center's syn0 row (reference SkipGram
                # HS semantics); the table rows ride the scan carry
                (lt.syn0, lt.syn1, pts_d, codes_d, cmask_d,
                 _) = learning.skipgram_hs_tables_scan(
                    lt.syn0, lt.syn1, pts_d, codes_d, cmask_d,
                    jnp.asarray(centers_p), jnp.asarray(contexts_p),
                    jnp.asarray(lr_vec))
                self._hs_tables_dev = (pts_d, codes_d, cmask_d)
            else:
                negs = self._stage_negatives(nb, nb_pad)
                scan_fn = (self._sharded_fns()[1]
                           if self.mesh is not None
                           else learning.skipgram_neg_scan)
                lt.syn0, lt.syn1neg, _ = scan_fn(
                    lt.syn0, lt.syn1neg, jnp.asarray(centers_p),
                    jnp.asarray(contexts_p), jnp.asarray(negs),
                    jnp.asarray(lr_vec))
            step_no += nb
        return step_no

    def _pad(self, arr: np.ndarray, value=0) -> np.ndarray:
        b = self.batch_size
        if len(arr) == b:
            return arr
        pad_shape = (b - len(arr),) + arr.shape[1:]
        return np.concatenate([arr, np.full(pad_shape, value, arr.dtype)])

    # one rng call refills this many batches of negatives at once — the
    # per-batch draw + unigram-table gather was a profiled host cost.
    # Sized to SCAN_CHUNK so a full scanned chunk consumes EXACTLY one
    # pool and _stage_negatives returns the pool itself, no concat copy
    # (r5: the slab concatenates were ~0.3s/epoch at v=100k)
    _NEG_POOL_BATCHES = SCAN_CHUNK

    def _sample_negatives(self) -> np.ndarray:
        """Next (batch_size, negative) block of negative samples. Drawn
        from a pooled pre-gathered buffer (one rng call + one table
        gather per _NEG_POOL_BATCHES batches); both the scanned and the
        stepped training paths consume this same stream, so their
        bit-level equivalence is preserved by construction. Always a
        FULL (batch_size, negative) row — partial final batches are
        padded upstream, and the old ``n`` parameter was ignored
        anyway (advisor r3), so it is gone."""
        pool = getattr(self, "_neg_pool", None)
        if pool is None or self._neg_cursor >= len(pool):
            self._refill_neg_pool()
            pool = self._neg_pool
        row = pool[self._neg_cursor]
        self._neg_cursor += 1
        return row

    def _refill_neg_pool(self) -> None:
        """Refill the pooled negative stream — the ONE definition both
        the per-batch and the slab (scanned) consumers share, so their
        draw streams are identical by construction. Native fill when
        the IO library is available (one numpy-Generator seed draw +
        xoshiro draws/gather in C++; r5: the numpy integers+gather
        refills were ~1s/epoch of GIL-held host time at v=100k), numpy
        fallback otherwise (int32 draw, no redundant astype copy)."""
        from deeplearning4j_tpu import native_bridge
        table = self.lookup_table.neg_table
        shape = (self._NEG_POOL_BATCHES, self.batch_size, self.negative)
        seed = int(self._rng.integers(0, 2 ** 63))
        pool = native_bridge.neg_pool_fill(table, shape, seed)
        if pool is None:
            picks = self._rng.integers(0, len(table), shape)
            pool = np.ascontiguousarray(
                table[picks].astype(np.int32, copy=False))
        self._neg_pool = pool
        self._neg_cursor = 0

    def _train_batch(self, centers: np.ndarray, contexts: np.ndarray,
                     lr: float) -> None:
        lt = self.lookup_table
        n = len(centers)
        lr_vec = np.zeros(self.batch_size, np.float32)
        lr_vec[:n] = lr
        centers_p = self._pad(centers)
        contexts_p = self._pad(contexts)
        if self.use_hs:
            codes = np.asarray(lt.codes)[contexts_p]
            cmask = np.asarray(lt.code_mask)[contexts_p]
            # hierarchical softmax: predict context's Huffman path from
            # the center vector (reference SkipGram HS semantics: the
            # *context* word's code/points, center's syn0 row)
            pts = np.asarray(lt.points)[contexts_p]
            lt.syn0, lt.syn1, _ = learning.skipgram_hs_step(
                lt.syn0, lt.syn1, jnp.asarray(centers_p),
                jnp.asarray(pts), jnp.asarray(codes), jnp.asarray(cmask),
                jnp.asarray(lr_vec))
            return
        if self.mesh is not None:
            step = self._sharded_fns()[0]
        else:
            step = learning.skipgram_neg_step
        lt.syn0, lt.syn1neg, _ = step(
            lt.syn0, lt.syn1neg, jnp.asarray(centers_p),
            jnp.asarray(contexts_p),
            jnp.asarray(self._sample_negatives()), jnp.asarray(lr_vec))
