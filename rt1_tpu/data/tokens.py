"""Packed-token host feed for the language-model family.

Synthetic documents (ids uniform over the vocabulary rows held, less the one
end-of-document id; lengths log-normal, clipped) are packed greedily, in
arrival order, into fixed-length sequences: a document and its
end-of-document id go into the open sequence if they fit, else the open
sequence is padded to its end and a new one begins.  Padding is only ever at a
sequence's tail.  Targets are the next token of the same sequence; padding,
and the last position before it, carry ``IGNORE``.

The documents' lengths and contents come from ``corpus_seed``; the order in
which they arrive comes from ``seed``.  A worker thread packs ahead of the
loop (``rt1/tokens/pack``); ``__next__`` hands a batch over
(``rt1/tokens/next``, with the count of batches ready).  Batches have the
trainer's shape: ``{"observations": {"tokens"}, "actions": {"targets"}}``,
ready for ``data.pipeline.device_feeder``.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Dict, Iterator, List

import numpy as np

from rt1_tpu.obs import startup
from rt1_tpu.obs import trace as obs_trace

IGNORE = -1     # models/lm/spec.py's; not imported, so that this module needs no jax


def document_lengths(corpus_seed: int, documents: int, median: float, sigma: float,
                     shortest: int, longest: int) -> np.ndarray:
    rng = np.random.default_rng([int(corpus_seed), 0])
    lengths = np.exp(rng.normal(np.log(median), sigma, documents))
    return np.clip(np.rint(lengths), shortest, longest).astype(np.int64)


class PackedTokenFeed:
    @startup.phased("open_feed")
    def __init__(self, *, batch_size: int, seq_len: int, vocab: int, seed: int,
                 corpus_seed: int = 20240801, documents: int = 4096,
                 doc_len_median: float = 1024, doc_len_sigma: float = 1.0,
                 doc_len_min: int = 16, depth: int = 2):
        if vocab < 2:
            raise ValueError("the vocabulary needs an end-of-document id and one more")
        self.batch_size, self.seq_len, self.vocab = int(batch_size), int(seq_len), int(vocab)
        self.eod = self.vocab - 1
        self.corpus_seed = int(corpus_seed)
        # a document keeps room for its end-of-document id
        self.lengths = document_lengths(
            corpus_seed, documents, doc_len_median, doc_len_sigma, doc_len_min,
            self.seq_len - 1)
        self._order_rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 1])
        self._arrivals: List[int] = []
        self._carry = None              # a document that did not fit the last sequence
        self.tokens_packed = 0
        self.padding_packed = 0
        self._queue: "queue.Queue[Any]" = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, name="rt1-tokens", daemon=True)
        self._thread.start()

    # -- documents

    def _document(self, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.corpus_seed, 1, index])
        body = rng.integers(0, self.eod, self.lengths[index], dtype=np.int32)
        return np.append(body, np.int32(self.eod))

    def _next_document(self) -> np.ndarray:
        if self._carry is not None:
            doc, self._carry = self._carry, None
            return doc
        if not self._arrivals:
            self._arrivals = list(self._order_rng.permutation(len(self.lengths))[::-1])
        return self._document(self._arrivals.pop())

    # -- packing

    def _sequence(self):
        tokens = np.full(self.seq_len, self.eod, np.int32)
        filled = 0
        while True:
            doc = self._next_document()
            if filled + len(doc) > self.seq_len:
                self._carry = doc
                break
            tokens[filled:filled + len(doc)] = doc
            filled += len(doc)
            if filled == self.seq_len:
                break
        targets = np.full(self.seq_len, IGNORE, np.int32)
        targets[:filled - 1] = tokens[1:filled]
        return tokens, targets, filled

    def pack_batch(self) -> Dict[str, Dict[str, np.ndarray]]:
        rows = [self._sequence() for _ in range(self.batch_size)]
        self.tokens_packed += sum(r[2] for r in rows)
        self.padding_packed += sum(self.seq_len - r[2] for r in rows)
        return {"observations": {"tokens": np.stack([r[0] for r in rows])},
                "actions": {"targets": np.stack([r[1] for r in rows])}}

    @property
    def padding_share(self) -> float:
        total = self.tokens_packed + self.padding_packed
        return self.padding_packed / total if total else 0.0

    def stats(self) -> Dict[str, float]:
        return {"ready_batches": float(self._queue.qsize()),
                "padding_share": float(self.padding_share)}

    # -- the worker and the loop's side

    def _work(self) -> None:
        ticket = 0
        try:
            while not self._stop.is_set():
                with obs_trace.span("tokens/pack", ticket=ticket):
                    batch = self.pack_batch()
                while not self._stop.is_set():
                    try:
                        self._queue.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                ticket += 1
        except Exception as exc:  # noqa: BLE001 - handed to the consumer, which raises it
            self._queue.put(exc)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        with obs_trace.span("tokens/next", ready=self._queue.qsize()):
            item = self._queue.get()
        if isinstance(item, BaseException):
            raise item
        return item

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


def feed_from_config(config, seed: int) -> PackedTokenFeed:
    lm = config.model.lm
    return PackedTokenFeed(
        batch_size=config.per_host_batch_size, seq_len=lm.seq_len, vocab=lm.vocab_held,
        seed=seed, corpus_seed=lm.corpus_seed, documents=lm.corpus_documents,
        doc_len_median=lm.doc_len_median, doc_len_sigma=lm.doc_len_sigma,
        doc_len_min=lm.doc_len_min)
