"""InvertedIndex (BASELINE config 4a): skewed reduce partitions.

Builds term -> sorted posting lists. Term frequencies are Zipfian, so a
handful of reducers receive most of the data — the skew case the
reference handled with its backlog/credit machinery (reference
src/DataNet/RDMAComm.cc:707-752) and that the TPU exchange handles with
multi-round windowing (uda_tpu.parallel.exchange).

On the chip this job is measured through the benchmark configuration
``benchmark/configs/invindex_text.json`` (cell ``reduce_invindex``): one
reduce task's partition of this module's ``<text_key(term), (doc, pos)>``
records, 16.4 M of them with no combiner, on the served reduce path.
Its generator draws a Zipf vocabulary of 2^20 lowercase words of 5-48
bytes (``benchmark/gen/invindex_mofs.py``) where ``zipf_corpus`` here
has 1,000 nine-byte terms; the key and value layout is this module's.
"""

from __future__ import annotations

import struct
from typing import Iterable, Optional

import numpy as np

from uda_tpu.models.pipeline import MapReduceJob, Record
from uda_tpu.models.wordcount import parse_text_key, text_key
from uda_tpu.utils.config import Config

__all__ = ["run_inverted_index", "zipf_corpus"]


def zipf_corpus(num_docs: int, words_per_doc: int, vocab: int = 1000,
                a: float = 1.5, seed: int = 0) -> list[tuple[int, list[bytes]]]:
    """Synthetic Zipf-distributed corpus: [(doc_id, [terms...])]."""
    rng = np.random.default_rng(seed)
    docs = []
    for d in range(num_docs):
        ids = np.minimum(rng.zipf(a, size=words_per_doc), vocab) - 1
        docs.append((d, [b"term%05d" % i for i in ids]))
    return docs


def _mapper(split) -> Iterable[Record]:
    for doc_id, terms in split:
        for pos, term in enumerate(terms):
            yield text_key(term), struct.pack(">II", doc_id, pos)


def _reducer(key: bytes, values: list[bytes]) -> Iterable[Record]:
    postings = sorted(struct.unpack(">II", v) for v in values)
    yield key, b"".join(struct.pack(">II", d, p) for d, p in postings)


def run_inverted_index(num_docs: int = 40, words_per_doc: int = 100,
                       num_maps: int = 4, num_reducers: int = 4,
                       seed: int = 0, config: Optional[Config] = None,
                       work_dir: Optional[str] = None
                       ) -> dict[bytes, list[tuple[int, int]]]:
    """Build the index; returns {term: [(doc, pos)...]} with each posting
    list sorted. Validity is checked against a direct computation."""
    corpus = zipf_corpus(num_docs, words_per_doc, seed=seed)
    splits = [corpus[i::num_maps] for i in range(num_maps)]
    job = MapReduceJob("invidx", _mapper, _reducer,
                       key_type="org.apache.hadoop.io.Text",
                       num_reducers=num_reducers, config=config,
                       work_dir=work_dir)
    outputs = job.run(splits)
    index: dict[bytes, list[tuple[int, int]]] = {}
    for recs in outputs.values():
        for k, v in recs:
            postings = [struct.unpack_from(">II", v, i)
                        for i in range(0, len(v), 8)]
            index[parse_text_key(k)] = postings
    # validity: recompute directly
    want: dict[bytes, list[tuple[int, int]]] = {}
    for doc_id, terms in corpus:
        for pos, term in enumerate(terms):
            want.setdefault(term, []).append((doc_id, pos))
    for term, postings in want.items():
        assert index.get(term) == sorted(postings), f"bad postings for {term!r}"
    return index
